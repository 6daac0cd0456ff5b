"""The analysis server: a persistent, admission-controlled loop.

One :class:`AnalysisServer` owns the device fleet for its lifetime.
Devices are partitioned into fixed sub-meshes exactly the way
:meth:`nbodykit_tpu.batch.TaskManager.map` partitions them
(:meth:`~nbodykit_tpu.batch.TaskManager.sub_meshes`), one long-lived
worker thread pinned per sub-mesh.  A request's life:

1. **submit** — priced by :func:`.admission.admit` against the
   sub-mesh HBM budget; a rejection (or a full queue) returns a
   structured :class:`RequestResult` immediately, never an exception.
2. **queue** — a single bounded priority view shared by the workers;
   ranking is priority desc, deadline asc, submission order.  Expired
   tickets are evicted WITH a structured verdict at every pop — a
   deadline miss is an answer, not a disappearance.
3. **place** — cache-affine: the worker at
   ``hash(program_key) % n_workers`` owns the warm executable; an
   idle worker steals the best-ranked foreign ticket rather than
   idle through a backlog.
4. **batch** — compatible clean-admission FFTPower tickets on a
   1-device sub-mesh coalesce into one vmap launch
   (:mod:`.batching`), the collection window capped so no member's
   deadline is blown.
5. **run** — under a per-request :class:`~nbodykit_tpu.resilience.Supervisor`
   (fault point ``serve.request.attempt``) with a request-scoped
   degradation ladder writing into THAT request's option overrides,
   applied via :func:`nbodykit_tpu.option_scope` — an injected device
   loss retries/degrades one request; the other tenants never see it.
   With a checkpoint store, finished work is saved before the
   post-work fault point ``serve.request.work`` so a kill after
   compute resumes instead of recomputing.
6. **deliver** — every submitted request ends as exactly one
   :class:`RequestResult`; ``lost`` (submitted minus resolved) is the
   number the doctor FAILs on.

Observability: ``serve.request`` spans, ``serve.*`` counters, a
``serve.queue_depth`` gauge and a ``serve.latency_s`` histogram; the
server additionally keeps the raw per-request latency list so
:meth:`AnalysisServer.summary` can report real p50/p99 (the streaming
histogram keeps only moments).
"""

import threading
import time

from ..diagnostics import (counter, current_tracer, gauge, histogram,
                           new_request_context, scope, span,
                           trace_context, trace_scope)
from ..diagnostics.export import FLIGHT, ensure_exporter, \
    register_source
from ..diagnostics.slo import SLOTracker
from ..parallel.runtime import mesh_size
from .admission import REJECT, admit
from .batching import BatchPolicy, close_window, compatible, pad_seeds
from .scheduler import ProgramCache, affinity, rank

# terminal request states
COMPLETED = 'completed'
REJECTED = 'rejected'
EVICTED = 'evicted'
FAILED = 'failed'


def _resolve_data_steal_grace(value):
    """The effective data-steal grace window in seconds: the
    ``data_steal_grace_s`` option when set, else
    ``$NBKIT_DATA_STEAL_GRACE_S``, else the class default (1.0).
    Must parse as a non-negative finite float (0 = steal freely)."""
    import math
    import os
    source = 'set_options(data_steal_grace_s=...)'
    if value in (None, 'auto'):
        value = os.environ.get('NBKIT_DATA_STEAL_GRACE_S')
        source = '$NBKIT_DATA_STEAL_GRACE_S'
        if value is None:
            return AnalysisServer.DATA_STEAL_GRACE_S
    try:
        grace = float(value)
    except (TypeError, ValueError):
        grace = -1.0
    if not math.isfinite(grace) or grace < 0:
        raise ValueError(
            'data_steal_grace_s must be a non-negative finite '
            'number of seconds, got %r (via %s)' % (value, source))
    return grace


class RequestResult(object):
    """The one terminal verdict every submitted request gets."""

    __slots__ = ('request_id', 'status', 'x', 'y', 'nmodes', 'reason',
                 'latency_s', 'events', 'options', 'admit_options',
                 'batch_size', 'algorithm', 'shape_class',
                 'queue_wait_s', 'service_s')

    def __init__(self, request_id, status, x=None, y=None, nmodes=None,
                 reason=None, latency_s=None, events=None, options=None,
                 admit_options=None, batch_size=0, algorithm=None,
                 shape_class=None, queue_wait_s=None, service_s=None):
        self.request_id = request_id
        self.status = status
        self.x, self.y, self.nmodes = x, y, nmodes
        self.reason = reason
        self.latency_s = latency_s
        # the latency split: time queued before a worker picked the
        # ticket vs time actually executing; latency_s remains the
        # combined end-to-end number for record compatibility
        self.queue_wait_s = queue_wait_s
        self.service_s = service_s
        self.events = list(events or [])
        # options: everything applied around the run (admission's
        # rungs and the runtime ladder's); admit_options: ONLY what
        # admission stepped down
        self.options = dict(options or {})
        self.admit_options = dict(admit_options or {})
        self.batch_size = int(batch_size)
        self.algorithm = algorithm
        self.shape_class = shape_class

    @property
    def ok(self):
        return self.status == COMPLETED

    def event_count(self, kind):
        return sum(1 for e in self.events if e.get('kind') == kind)

    def to_dict(self):
        out = {'request_id': self.request_id, 'status': self.status,
               'latency_s': self.latency_s,
               'queue_wait_s': self.queue_wait_s,
               'service_s': self.service_s,
               'batch_size': self.batch_size,
               'algorithm': self.algorithm,
               'shape_class': self.shape_class,
               'options': dict(self.options),
               'admit_options': dict(self.admit_options),
               'events': list(self.events)}
        if self.reason is not None:
            out['reason'] = dict(self.reason)
        return out

    def __repr__(self):
        return 'RequestResult(%s %s%s)' % (
            self.request_id, self.status,
            ' %.3fs' % self.latency_s if self.latency_s else '')


class _Ticket(object):
    __slots__ = ('request', 'decision', 'submitted_at', 'deadline_at',
                 'seq', 'affinity', 'done', 'result', 'verify', 'ctx',
                 'ctx_owned')

    def __init__(self, request, decision, submitted_at, seq, aff,
                 verify=False, ctx=None, ctx_owned=False):
        self.request = request
        self.decision = decision
        self.submitted_at = submitted_at
        self.deadline_at = submitted_at + request.deadline_s
        self.seq = seq
        self.affinity = aff
        self.done = threading.Event()
        self.result = None
        self.verify = bool(verify)
        # the request's trace context, carried explicitly because
        # worker threads outlive (and predate) every request — the
        # contextvar cannot reach them (trace.py)
        self.ctx = ctx
        self.ctx_owned = bool(ctx_owned)


class AnalysisServer(object):
    """Multi-tenant FFTPower-as-a-service over the local device fleet.

    Parameters
    ----------
    per_task : devices per sub-mesh (1 → every worker is a 1-device
        batchable lane; the fleet is ``n_devices // per_task`` lanes)
    max_queue : bound on waiting tickets; beyond it submissions get a
        structured ``queue_full`` rejection
    hbm_bytes : per-device HBM the admission controller prices against
        (0.85x of this is the budget); by default what the fleet's
        first device reports (:func:`~nbodykit_tpu.pmesh.
        device_hbm_bytes`)
    batch : :class:`.batching.BatchPolicy`
    checkpoint : :class:`~nbodykit_tpu.resilience.CheckpointStore`
        or None — per-request resume across mid-run faults
    retry : :class:`~nbodykit_tpu.resilience.RetryPolicy` override
    verify_fraction : float in [0, 1] — deterministically sample this
        fraction of admitted seeded requests for tier-1 shadow
        verification (docs/INTEGRITY.md), on top of any request that
        sets ``verify=True`` itself.  A shadowed request re-executes
        on a different sub-mesh worker after completion and the
        results are compared — bit-identical when no lossy
        compression is in play, within :func:`~nbodykit_tpu.resilience
        .integrity.shadow_margin` otherwise.  A mismatch raises a
        classified IntegrityError, so the per-request Supervisor
        retries it once and the strike lands in the SuspectTracker.
        The shadow run needs no extra admission headroom: it executes
        the SAME priced program on the shadow worker's identical
        sub-mesh, so the request's memory_plan verdict bounds both
        executions.
    """

    def __init__(self, per_task=1, max_queue=256, hbm_bytes=None,
                 batch=None, checkpoint=None, retry=None,
                 verify_fraction=0.0, name=None):
        from ..batch import TaskManager
        from ..parallel.runtime import (CurrentMesh, cpu_mesh,
                                        tpu_mesh, use_mesh)
        from ..utils import is_mxu_backend
        if CurrentMesh.get() is None:
            # no ambient fleet mesh: serve the whole local device set
            fleet = tpu_mesh() if is_mxu_backend() else cpu_mesh()
            with use_mesh(fleet):
                tm = TaskManager(per_task)
                self.meshes = tm.sub_meshes()
        else:
            tm = TaskManager(per_task)
            self.meshes = tm.sub_meshes()
        if not self.meshes:
            raise RuntimeError('no device sub-meshes to serve on')
        self.ndevices = mesh_size(self.meshes[0])
        self.max_queue = int(max_queue)
        if hbm_bytes is None:
            from ..pmesh import device_hbm_bytes
            hbm_bytes = device_hbm_bytes(self.meshes[0].devices.flat[0])
        self.hbm_bytes = float(hbm_bytes)
        self.batch = batch if batch is not None else BatchPolicy()
        self.checkpoint = checkpoint
        self.retry = retry
        self.verify_fraction = min(max(float(verify_fraction), 0.0),
                                   1.0)
        self._shadow = {'verified': 0, 'mismatch': 0}
        self.programs = ProgramCache()
        # one content-addressed catalog cache per sub-mesh worker:
        # repeat data_ref requests against a survey route (via the
        # path-salted affinity) to the worker already holding it.
        # 'ingest_cache_bytes' is an optional hard cap; the per-request
        # memory_plan predicate (admission.catalog_fits_fn) prices
        # eviction either way.
        from .. import _global_options
        from ..ingest.cache import CatalogCache
        _cb = _global_options['ingest_cache_bytes']
        _cb = int(_cb) if isinstance(_cb, (int, float)) \
            and not isinstance(_cb, bool) else None
        self.catalogs = [CatalogCache(_cb) for _ in self.meshes]
        self.data_steal_grace_s = _resolve_data_steal_grace(
            _global_options['data_steal_grace_s'])

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._pending = []
        self._inflight = 0
        self._seq = 0
        self._stop = False
        self._accepting = True
        self._started_at = time.monotonic()

        self.results = {}
        self._latencies = []
        self._queue_waits = []
        self._service_times = []
        self._submitted = 0
        # the fleet label for the export plane's per-fleet gauges
        # (serve.queue_depth{fleet=...}); a Region names its fleets,
        # a standalone server may pass name= itself
        self.name = str(name) if name else None
        # per-shape-class SLO burn tracking; a Region layers its own
        # per-tenant-class tracker above this one
        self.slo = SLOTracker()
        register_source('serve%s' % ('.' + self.name if self.name
                                     else ''), self.slo.snapshot)
        ensure_exporter()

        self._threads = [
            threading.Thread(target=self._worker, args=(i,),
                             name='serve-worker-%d' % i, daemon=True)
            for i in range(len(self.meshes))]
        for t in self._threads:
            t.start()

    # -- lifecycle --------------------------------------------------------

    def set_name(self, name):
        """Label this fleet for the export plane (a Region names its
        member fleets at wrap time); re-registers the SLO source under
        the labelled name."""
        self.name = str(name)
        register_source('serve.' + self.name, self.slo.snapshot)
        return self

    def _depth_gauge(self, depth, inflight=None):
        """The queue-depth (and optionally inflight) gauges, both the
        process-global compatibility name and the per-fleet labelled
        series the router's spill decisions are audited against."""
        gauge('serve.queue_depth').set(depth)
        if self.name:
            gauge('serve.queue_depth', fleet=self.name).set(depth)
        if inflight is not None and self.name:
            gauge('serve.inflight', fleet=self.name).set(inflight)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()

    def drain(self, timeout=None):
        """Block until every accepted ticket has a result (the queue is
        empty and no worker is mid-request).  Returns True when fully
        drained."""
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._cv:
            while self._pending or self._inflight:
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._cv.wait(timeout=left if left is not None
                              else 0.5)
        return True

    def shutdown(self, drain=True, timeout=None):
        """Stop accepting, optionally drain what was accepted, stop
        the workers.  Idempotent — a second call is a no-op."""
        with self._cv:
            self._accepting = False
            already = self._stop
        if not already and drain:
            self.drain(timeout=timeout)
        with self._cv:
            # anything still queued (drain=False or timed out) gets a
            # structured eviction, never silence
            for t in self._pending:
                self._finish(t, RequestResult(
                    t.request.request_id, EVICTED,
                    reason={'code': 'shutdown',
                            'detail': 'server shut down before run'},
                    algorithm=t.request.algorithm,
                    shape_class=t.request.shape_class))
            self._pending = []
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)

    def preempt(self, grace_s=5.0):
        """Preemption drain: the SIGTERM response for a serving
        process (docs/RESILIENCE.md).  Stops accepting, EVICTS every
        queued ticket immediately with a structured ``preempted``
        verdict (inflight work is worth the grace budget; queued work
        is not — the client retries elsewhere), drains inflight
        requests for up to ``grace_s``, then stops the workers.  Every
        submitted request still ends with a verdict — zero lost.
        Returns ``{'evicted': n, 'drained': bool}``."""
        counter('serve.preempted').add(1)
        from ..diagnostics import current_tracer
        tr = current_tracer()
        if tr is not None:
            tr.event('resilience.preempted', {'where': 'serve'})
        with self._cv:
            self._accepting = False
            evicted = list(self._pending)
            self._pending = []
            self._depth_gauge(0)
            for t in evicted:
                self._finish(t, RequestResult(
                    t.request.request_id, EVICTED,
                    reason={'code': 'preempted',
                            'detail': 'server preempted before run'},
                    algorithm=t.request.algorithm,
                    shape_class=t.request.shape_class))
            self._cv.notify_all()
        drained = self.drain(timeout=grace_s)
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        # seal the flight recorder: the last N request waterfalls +
        # metric snapshot land next to the trace for the post-mortem
        FLIGHT.dump('serve.preempt%s' % ('.' + self.name
                                         if self.name else ''))
        return {'evicted': len(evicted), 'drained': drained}

    # -- submission -------------------------------------------------------

    def submit(self, request):
        """Admit (or reject) ``request`` and queue it.  Returns a
        ticket whose ``.done`` event / ``.result`` carry the verdict;
        rejections resolve immediately."""
        now = time.monotonic()
        counter('serve.submitted').add(1)
        # trace identity: adopt the caller's ambient context (a Region
        # dispatching) or mint a fresh one — either way the ticket
        # carries it across the queue to the worker thread
        ctx = trace_context()
        owns_ctx = ctx is None
        if owns_ctx and current_tracer() is not None:
            ctx = new_request_context(request.request_id)
        # a scope, not a bare span: the client thread's half of the
        # request is on the profiler's host line and the host ledger too
        with trace_scope(ctx if owns_ctx else None), \
                scope('serve.submit', request_id=request.request_id,
                      algorithm=request.algorithm,
                      shape_class=request.shape_class) as sp:
            if owns_ctx and ctx is not None and not ctx.span_id:
                # this span IS the request's root: every cross-thread
                # span re-parents to it via ctx.span_id
                ctx.span_id = sp.span_id
            return self._submit_traced(request, now, ctx, owns_ctx)

    def _submit_traced(self, request, now, ctx, owns_ctx):
        with self._lock:
            self._submitted += 1
            accepting = self._accepting
            depth = len(self._pending)
        aff = affinity(request, self.ndevices, len(self.meshes))
        if not accepting:
            from ..resilience.fleet import preemption_requested
            if preemption_requested():
                return self._reject_now(request, now, {
                    'code': 'preempted',
                    'detail': 'server preempted; retry elsewhere'},
                    ctx=ctx, ctx_owned=owns_ctx)
            return self._reject_now(request, now, {
                'code': 'shutting_down',
                'detail': 'server no longer accepting requests'},
                ctx=ctx, ctx_owned=owns_ctx)
        if depth >= self.max_queue:
            return self._reject_now(request, now, {
                'code': 'queue_full', 'depth': depth,
                'max_queue': self.max_queue,
                'detail': 'bounded queue at capacity'},
                ctx=ctx, ctx_owned=owns_ctx)
        decision = admit(request, ndevices=self.ndevices,
                         hbm_bytes=self.hbm_bytes)
        if decision.status == REJECT:
            return self._reject_now(request, now, decision.reason,
                                    decision=decision, ctx=ctx,
                                    ctx_owned=owns_ctx)
        if decision.options:
            counter('serve.admit_degraded').add(1)
        ticket = None
        with self._cv:
            self._seq += 1
            ticket = _Ticket(request, decision, now, self._seq, aff,
                             verify=self._should_verify(request),
                             ctx=ctx, ctx_owned=owns_ctx)
            self._pending.append(ticket)
            self._depth_gauge(len(self._pending))
            self._cv.notify_all()
        return ticket

    def _should_verify(self, request):
        """Whether this request gets a tier-1 shadow run: opted in via
        ``request.verify``, or deterministically sampled (a stable
        hash of the request id, not a PRNG — the same request stream
        shadows the same requests on every replay, so admission-level
        A/B comparisons stay reproducible).  data_ref requests never
        shadow (re-ingestion is not a cheap re-execution)."""
        if getattr(request, 'data_ref', None) is not None:
            return False
        if getattr(request, 'verify', False):
            return True
        if self.verify_fraction <= 0.0:
            return False
        import zlib
        h = zlib.crc32(request.request_id.encode('utf-8')) % 10000
        return h < self.verify_fraction * 10000.0

    def _reject_now(self, request, now, reason, decision=None,
                    ctx=None, ctx_owned=False):
        counter('serve.rejected').add(1)
        t = _Ticket(request, decision, now, -1, -1, ctx=ctx,
                    ctx_owned=ctx_owned)
        self._finish(t, RequestResult(
            request.request_id, REJECTED, reason=reason,
            latency_s=time.monotonic() - now,
            algorithm=request.algorithm,
            shape_class=request.shape_class))
        return t

    def wait(self, ticket, timeout=None):
        """Block for a ticket's terminal :class:`RequestResult`."""
        ticket.done.wait(timeout=timeout)
        return ticket.result

    # -- the worker loop --------------------------------------------------

    def _finish(self, ticket, result):
        # the terminal mark, as a scope: stamped into the request's
        # own trace whichever thread finishes it, and the delivery's
        # host time on the ledger under its own name
        with trace_scope(ticket.ctx), \
                scope('serve.deliver', request_id=result.request_id,
                      status=result.status, latency_s=result.latency_s):
            self._deliver(ticket, result)

    def _deliver(self, ticket, result):
        ticket.result = result
        self.results[result.request_id] = result
        if result.status == COMPLETED:
            counter('serve.completed').add(1)
            if result.latency_s is not None:
                histogram('serve.latency_s').observe(result.latency_s)
                self._latencies.append(result.latency_s)
            if result.queue_wait_s is not None:
                self._queue_waits.append(result.queue_wait_s)
            if result.service_s is not None:
                self._service_times.append(result.service_s)
        elif result.status == FAILED:
            counter('serve.failed').add(1)
        elif result.status == EVICTED:
            counter('serve.evicted').add(1)
        # the SLO stream: deadline evictions burn budget, shutdown /
        # preemption / admission shedding does not (slo.py)
        if result.status == EVICTED:
            code = (result.reason or {}).get('code')
            slo_status = 'deadline_evicted' if code == 'deadline' \
                else 'cancelled'
        else:
            slo_status = result.status
        self.slo.observe(result.shape_class or 'default',
                         result.latency_s, slo_status)
        if ticket.ctx_owned:
            # front-door-less serving: this server owns the request's
            # flight-recorder entry (a Region records its own)
            FLIGHT.record({
                'request_id': result.request_id,
                'trace': ticket.ctx.trace_id if ticket.ctx else None,
                'status': result.status,
                'latency_s': result.latency_s,
                'queue_wait_s': result.queue_wait_s,
                'service_s': result.service_s,
                'shape_class': result.shape_class})
        ticket.done.set()

    def _evict_expired_locked(self, now):
        live = []
        for t in self._pending:
            if now >= t.deadline_at:
                self._finish(t, RequestResult(
                    t.request.request_id, EVICTED,
                    reason={'code': 'deadline',
                            'deadline_s': t.request.deadline_s,
                            'waited_s': round(now - t.submitted_at, 3),
                            'detail': 'deadline passed while queued'},
                    latency_s=now - t.submitted_at,
                    algorithm=t.request.algorithm,
                    shape_class=t.request.shape_class))
            else:
                live.append(t)
        self._pending = live

    # How long a data_ref ticket is reserved for its affinity worker
    # before any idle worker may steal it.  A steal pays a full
    # re-ingest onto a cold CatalogCache, so locality is worth a short
    # wait — but only a short one: a wedged affinity worker must not
    # strand the request (deadline eviction is not a placement policy).
    # The instance value resolves set_options(data_steal_grace_s=...)
    # / $NBKIT_DATA_STEAL_GRACE_S at construction; this class attr is
    # the documented default.
    DATA_STEAL_GRACE_S = 1.0

    def _pick_locked(self, wi, now):
        """Best ticket for worker ``wi``: its own affinity first, else
        steal the globally best-ranked one.  data_ref tickets resist
        stealing for ``data_steal_grace_s`` — their catalog may be
        resident in the affinity worker's cache."""
        mine = [t for t in self._pending if t.affinity == wi]
        pool = mine or [t for t in self._pending
                        if t.request.data_ref is None
                        or now - t.submitted_at
                        >= self.data_steal_grace_s]
        if not pool:
            return None
        best = min(pool, key=rank)
        self._pending.remove(best)
        return best

    def _batchable(self, ticket):
        # data_ref requests never batch: their input is a streamed
        # catalog, not a seed vmap can widen over.  Shadow-verified
        # tickets never batch either: the shadow re-run and compare
        # are per-request, and one suspect member must not force a
        # whole batch through a second execution.
        return (self.ndevices == 1
                and ticket.request.algorithm in ('FFTPower',
                                                 'Bispectrum')
                and ticket.request.data_ref is None
                and not ticket.verify
                and not ticket.decision.options)

    def _collect_locked(self, leader, opened_at):
        """Grow the leader's batch from compatible pending tickets,
        holding the coalescing window open at most ``max_delay_s`` and
        never past any member's deadline."""
        group = [leader]
        if not self._batchable(leader) \
                or self.batch.max_batch <= 1 \
                or self.batch.max_delay_s <= 0:
            return group
        while True:
            for t in list(self._pending):
                if len(group) >= self.batch.max_batch:
                    break
                if self._batchable(t) and compatible(leader, t,
                                                     self.ndevices):
                    self._pending.remove(t)
                    group.append(t)
            now = time.monotonic()
            if self._stop or close_window(now, group, self.batch,
                                          opened_at):
                return group
            self._cv.wait(timeout=self.batch.max_delay_s / 4 or 0.01)

    def _worker(self, wi):
        mesh = self.meshes[wi]
        while True:
            with self._cv:
                while True:
                    if self._stop:
                        return
                    now = time.monotonic()
                    self._evict_expired_locked(now)
                    ticket = self._pick_locked(wi, now)
                    if ticket is not None:
                        break
                    self._cv.wait(timeout=0.25)
                group = self._collect_locked(ticket, time.monotonic())
                self._inflight += 1
                self._depth_gauge(len(self._pending),
                                  inflight=self._inflight)
            try:
                self._run_group(group, mesh, wi)
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()

    # -- execution --------------------------------------------------------

    def _run_group(self, group, mesh, wi):
        import nbodykit_tpu
        from ..resilience import Supervisor
        from ..resilience.faults import fault_point
        from ..resilience.supervise import scoped_ladder

        leader = group[0]
        req = leader.request
        if len(group) > 1:
            counter('serve.batched').add(len(group))
        # one mutable option dict per run: admission's rungs seed it,
        # the supervisor's runtime ladder steps it further on OOM —
        # both scoped to this run, applied only inside option_scope
        opts = dict(leader.decision.options or {})
        sup = Supervisor('serve.request', policy=self.retry,
                         ladder=scoped_ladder(opts),
                         checkpoint=self.checkpoint)
        seeds = [t.request.seed for t in group]
        rid = req.request_id
        ingest_stats = {}

        def work():
            if req.data_ref is not None:
                return work_data()
            got = sup.resume(rid, validate=lambda s:
                             s.get('seeds') == list(seeds))
            if got is not None:
                state, arrays = got
                n = len(seeds)
                return [(arrays['x'][i], arrays['y'][i],
                         arrays['nm'][i]) for i in range(n)]
            with nbodykit_tpu.option_scope(**opts):
                prog = self.programs.get(req, mesh, wi, opts=opts)
                if prog.batchable:
                    padded, n = pad_seeds(seeds)
                    out = prog.run(padded)[:n]
                else:
                    out = prog.run(seeds)
                # the serve.result data-injection point sits HERE —
                # after compute, before verification and checkpoint —
                # so only the tier-1 shadow compare can catch it
                out = self._result_corrupt_point(out)
                if leader.verify:
                    # verify BEFORE sup.save: a corrupted result must
                    # never be checkpointed, or the retry would resume
                    # it instead of recomputing clean
                    self._shadow_verify(req, out, seeds, opts, wi)
            import numpy as np
            sup.save(rid, {'seeds': list(seeds)},
                     arrays={'x': np.array([o[0] for o in out]),
                             'y': np.array([o[1] for o in out]),
                             'nm': np.array([o[2] for o in out])})
            # the post-work fault point: a kill injected here lands
            # AFTER the checkpoint, so the retry resumes, not recomputes
            fault_point('serve.request.work')
            return out

        def work_data():
            # the streamed-catalog path: never batched (group is just
            # the leader), cache-hit routed straight to paint, evicting
            # under this request's own memory_plan predicate
            got = sup.resume(rid, validate=lambda s:
                             s.get('data_path')
                             == req.data_ref.get('path'))
            if got is not None:
                state, arrays = got
                ingest_stats.update(state.get('ingest') or {})
                return [(arrays['x'][0], arrays['y'][0],
                         arrays['nm'][0])]
            from .admission import catalog_fits_fn
            fits = catalog_fits_fn(req, ndevices=self.ndevices,
                                   hbm_bytes=self.hbm_bytes)
            counter('serve.data_requests').add(1)
            with nbodykit_tpu.option_scope(**opts):
                prog = self.programs.get(req, mesh, wi, opts=opts)
                out, stats = prog.run_data(req.data_ref,
                                           cache=self.catalogs[wi],
                                           fits=fits)
            ingest_stats.update(stats)
            import numpy as np
            sup.save(rid, {'data_path': req.data_ref.get('path'),
                           'ingest': {k: v for k, v in stats.items()
                                      if not isinstance(v, bytes)}},
                     arrays={'x': np.array([o[0] for o in out]),
                             'y': np.array([o[1] for o in out]),
                             'nm': np.array([o[2] for o in out])})
            fault_point('serve.request.work')
            return out

        now = time.monotonic()
        # the queue -> worker thread hop: re-activate the leader's
        # context (contextvars never reach this long-lived thread) and
        # retro-emit each member's queue wait into ITS OWN trace, plus
        # a zero-duration link span tying member traces to the
        # leader's (the batch runs once, under the leader's identity)
        tr = current_tracer()
        if tr is not None:
            wall = time.time()
            for t in group:
                qw = max(now - t.submitted_at, 0.0)
                if t.ctx is not None:
                    tr.emit_span('serve.queue.wait', wall - qw, qw,
                                 {'request_id': t.request.request_id,
                                  'worker': wi}, ctx=t.ctx)
            if leader.ctx is not None:
                for t in group[1:]:
                    if t.ctx is not None:
                        tr.emit_span(
                            'serve.batch.member', wall, 0.0,
                            {'request_id': t.request.request_id,
                             'leader_trace': leader.ctx.trace_id,
                             'leader_request': rid}, ctx=t.ctx)
        # the worker's root: a scope, so that the request's host time
        # is on the profiler's host line and the ledger by name
        with trace_scope(leader.ctx), \
                scope('serve.request', request_id=rid,
                      algorithm=req.algorithm,
                      shape_class=req.shape_class,
                      batch=len(group), worker=wi):
            try:
                out, failed = sup.run(work), None
            except Exception as e:
                failed = e
        if failed is not None:
            # delivered outside the leader's span: each member's
            # ``serve.deliver`` hangs off its own request's root
            done_at = time.monotonic()
            for t in group:
                self._finish(t, RequestResult(
                    t.request.request_id, FAILED,
                    reason={'code': 'execution',
                            'error': str(failed)[:500],
                            'type': type(failed).__name__},
                    latency_s=done_at - t.submitted_at,
                    events=sup.events, options=opts,
                    admit_options=t.decision.options,
                    batch_size=len(group),
                    algorithm=t.request.algorithm,
                    shape_class=t.request.shape_class,
                    queue_wait_s=now - t.submitted_at,
                    service_s=done_at - now))
            return
        sup.done(rid)
        if sup.events:
            counter('serve.fault_degraded').add(1)
        done_at = time.monotonic()
        events = list(sup.events)
        if ingest_stats:
            # the per-request ingestion record (cache_hit, bytes,
            # seconds, chunk_rows, host peak) rides on the result as
            # an event — bench --ingest and the doctor read it there
            events.append(dict(ingest_stats, kind='ingest'))
        for t, (x, y, nm) in zip(group, out):
            self._finish(t, RequestResult(
                t.request.request_id, COMPLETED, x=x, y=y, nmodes=nm,
                latency_s=done_at - t.submitted_at, events=events,
                options=opts, admit_options=t.decision.options,
                batch_size=len(group),
                algorithm=t.request.algorithm,
                shape_class=t.request.shape_class,
                queue_wait_s=now - t.submitted_at,
                service_s=done_at - now))

    # -- tier-1 shadow verification ---------------------------------------

    def _result_corrupt_point(self, out):
        """The ``serve.result`` data-injection point: flip bits in the
        delivered spectrum of the first result when a ``corrupt`` rule
        fires (chaos grammar, docs/INTEGRITY.md).  The corruption is
        applied to the REAL result the shadow compare judges — the
        detector is what gets tested, not the injector."""
        from ..resilience.faults import corrupt_spec
        bits = corrupt_spec('serve.result')
        if not bits:
            return out
        from ..resilience.integrity import corrupt_host
        x, y, nm = out[0]
        return [(x, corrupt_host(y, bits), nm)] + list(out[1:])

    def _shadow_verify(self, req, out, seeds, opts, wi):
        """Re-execute ``req`` on the next sub-mesh worker's devices
        and compare against ``out``.  Uncompressed postures must match
        bit-for-bit (same XLA program, same backend — any divergence
        is hardware or wire corruption); compressed postures are
        judged against :func:`~nbodykit_tpu.resilience.integrity
        .shadow_margin`.  A mismatch raises a recorded
        IntegrityError(``serve.shadow``), which the per-request
        Supervisor classifies, strikes, and retries exactly once."""
        import numpy as np
        from ..resilience.integrity import shadow_margin, violation
        swi = (wi + 1) % len(self.meshes)
        with span('serve.shadow_verify', request_id=req.request_id,
                  worker=wi, shadow_worker=swi):
            sprog = self.programs.get(req, self.meshes[swi], swi,
                                      opts=opts)
            if sprog.batchable:
                padded, n = pad_seeds(seeds)
                ref = sprog.run(padded)[:n]
            else:
                ref = sprog.run(seeds)
        margin = shadow_margin(opts)
        counter('serve.shadow.verified').add(1)
        with self._lock:
            self._shadow['verified'] += 1
        for (x1, y1, n1), (x2, y2, n2) in zip(out, ref):
            delta, bad = None, None
            if not np.array_equal(np.asarray(x1), np.asarray(x2)) \
                    or not np.array_equal(np.asarray(n1),
                                          np.asarray(n2)):
                bad = 'bin geometry diverged'
            else:
                a = np.asarray(y1, np.float64)
                b = np.asarray(y2, np.float64)
                if margin <= 0.0:
                    if not np.array_equal(a, b):
                        delta = float(np.max(np.abs(a - b)))
                        bad = 'bit-identical required'
                else:
                    scale = max(float(np.max(np.abs(b))), 1e-30)
                    delta = float(np.max(np.abs(a - b))) / scale
                    if delta > margin:
                        bad = 'relative margin %.3g exceeded' % margin
                    else:
                        delta, bad = None, None
            if bad is not None:
                counter('serve.shadow.mismatch').add(1)
                with self._lock:
                    self._shadow['mismatch'] += 1
                raise violation(
                    'serve.shadow', delta=delta,
                    detail='%s (request %s, worker %d vs shadow %d)'
                           % (bad, req.request_id, wi, swi))

    # -- reporting --------------------------------------------------------

    @staticmethod
    def _pctile(values, q):
        if not values:
            return None
        vs = sorted(values)
        idx = min(len(vs) - 1, max(0, int(round(q * (len(vs) - 1)))))
        return vs[idx]

    def load(self):
        """The live load/health surface a region router probes before
        every placement: queue depth, inflight work, and whether this
        fleet still accepts — one lock, no device work, cheap enough
        to call per-route (``summary()`` is the full scorecard; this
        is the heartbeat)."""
        with self._lock:
            return {'queued': len(self._pending),
                    'inflight': self._inflight,
                    'accepting': self._accepting and not self._stop,
                    'workers': len(self.meshes)}

    def summary(self):
        """The serving scorecard: totals by terminal status, real
        p50/p99 latency, throughput, degradation provenance
        (``admit_degraded`` = stepped down at pricing;
        ``fault_degraded`` = supervisor events at runtime), and
        ``lost`` — submitted requests with NO structured verdict,
        the number that must be zero."""
        with self._lock:
            results = list(self.results.values())
            lat = list(self._latencies)
            qwaits = list(self._queue_waits)
            stimes = list(self._service_times)
            submitted = self._submitted
            queued = len(self._pending)
            inflight = self._inflight
            shadow = dict(self._shadow)
        by_status = {}
        for r in results:
            by_status[r.status] = by_status.get(r.status, 0) + 1
        by_class = {}
        for r in results:
            if r.status == COMPLETED and r.latency_s is not None:
                by_class.setdefault(r.shape_class, []).append(
                    r.latency_s)
        completed = by_status.get(COMPLETED, 0)
        wall = max(time.monotonic() - self._started_at, 1e-9)
        retried = sum(1 for r in results
                      if r.event_count('retries'))
        degraded = sum(1 for r in results
                       if r.event_count('degradations'))
        resumed = sum(1 for r in results if r.event_count('resumes'))
        admit_deg = sum(1 for r in results if r.admit_options)
        preempted = sum(
            1 for r in results
            if (r.reason or {}).get('code') == 'preempted')
        ingest_events = [e for r in results for e in r.events
                         if e.get('kind') == 'ingest']
        cat = {'entries': 0, 'resident_bytes': 0, 'hits': 0,
               'misses': 0, 'evictions': 0}
        for c in self.catalogs:
            for k, v in c.stats().items():
                cat[k] += v
        return {
            'submitted': submitted,
            'resolved': len(results),
            'lost': submitted - len(results) - queued - inflight,
            'completed': completed,
            'rejected': by_status.get(REJECTED, 0),
            'evicted': by_status.get(EVICTED, 0),
            'failed': by_status.get(FAILED, 0),
            'retried': retried,
            'fault_degraded': degraded,
            'resumed': resumed,
            'admit_degraded': admit_deg,
            'preempted': preempted,
            'p50_s': self._pctile(lat, 0.50),
            'p99_s': self._pctile(lat, 0.99),
            'mean_s': sum(lat) / len(lat) if lat else None,
            # the split the combined numbers above conflate: time
            # queued before a worker picked the ticket vs time
            # actually executing (queue_wait + service = latency for
            # unbatched requests; batched members share the service
            # window, so the split is per-request exact either way)
            'queue_p50_s': self._pctile(qwaits, 0.50),
            'queue_p99_s': self._pctile(qwaits, 0.99),
            'queue_mean_s': sum(qwaits) / len(qwaits)
            if qwaits else None,
            'service_p50_s': self._pctile(stimes, 0.50),
            'service_p99_s': self._pctile(stimes, 0.99),
            'service_mean_s': sum(stimes) / len(stimes)
            if stimes else None,
            'rps': completed / wall,
            'wall_s': wall,
            'workers': len(self.meshes),
            'ndevices_per_worker': self.ndevices,
            'programs': len(self.programs),
            # the ingestion posture: how many completed requests
            # streamed a catalog, how many of those were served from
            # the on-device cache, and the fleet-wide cache counters
            # (the doctor's thrash verdict reads evictions vs hits)
            'ingest_requests': len(ingest_events),
            'ingest_cache_hits': sum(
                1 for e in ingest_events if e.get('cache_hit')),
            'ingest_gb': round(sum(
                float(e.get('bytes') or 0)
                for e in ingest_events) / 1e9, 6),
            'ingest_cache': cat,
            # the tier-1 integrity posture (docs/INTEGRITY.md):
            # shadowed runs, mismatches caught, and how many requests
            # recovered through the Supervisor's one integrity retry —
            # the doctor FAILs when mismatches outnumber recoveries
            'shadow_verified': shadow['verified'],
            'shadow_mismatch': shadow['mismatch'],
            'integrity_retried': sum(
                1 for r in results
                if r.event_count('integrity_retries')),
            'by_class': {k: {'n': len(v),
                             'p50_s': self._pctile(v, 0.50),
                             'p99_s': self._pctile(v, 0.99)}
                         for k, v in sorted(by_class.items())},
            # per-shape-class SLO burn verdicts (diagnostics/slo.py)
            'slo': self.slo.snapshot(),
        }
