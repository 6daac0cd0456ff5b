"""nbodykit_tpu.serve — FFTPower-as-a-service.

The batch pipeline answers "run my analysis"; this package answers
"run EVERYONE'S analyses, continuously, on one shared fleet" — the
operating regime of a survey-scale TPU pod: a persistent,
admission-controlled, multi-tenant analysis server.

- :mod:`.request` — the declarative :class:`AnalysisRequest` (what to
  compute + deadline + priority; a few hundred bytes, no arrays —
  real-survey requests point at their catalog with ``data_ref``
  instead of ``seed`` and the ingestion plane
  (:mod:`nbodykit_tpu.ingest`) streams it onto the sub-mesh).
- :mod:`.admission` — every request priced through
  :func:`~nbodykit_tpu.pmesh.memory_plan` against the sub-mesh HBM
  budget BEFORE scheduling: admit, degrade down the request-scoped
  resilience ladder, or reject with a structured reason.
- :mod:`.scheduler` — cache-affine placement onto
  :meth:`~nbodykit_tpu.batch.TaskManager.sub_meshes` workers and the
  warm :class:`ProgramCache` (``compile.serve.*`` counters prove the
  second identical-shape request compiles nothing).
- :mod:`.batching` — compatible FFTPower requests vmap-coalesced into
  one device launch, the window bounded so no deadline is blown.
- :mod:`.server` — the :class:`AnalysisServer` loop: bounded queue,
  deadline eviction with structured verdicts, per-request
  Supervisor + option scope (one tenant's fault never touches the
  fleet), graceful drain/shutdown.
- :mod:`.synth` — deterministic Zipf-popularity request traces for
  the bench/regress pipeline (``bench.py --serve-trace``,
  ``--region-trace``).
- :mod:`.region` — the layer ABOVE the fleet: a :class:`Region`
  fronts N independent servers with catalog-affine routing +
  least-loaded spill, content-addressed result memoization
  (:class:`ResultCache`), per-tenant QoS fair share
  (:class:`QoSPolicy`), and elastic membership grow sealed with
  ``reformed_from/to`` stamps (docs/SERVING.md "Region").

Quick start::

    from nbodykit_tpu.serve import AnalysisServer, AnalysisRequest
    with AnalysisServer(per_task=1) as srv:
        t = srv.submit(AnalysisRequest(nmesh=64, npart=100000))
        result = srv.wait(t)       # RequestResult: k, P(k), nmodes

CLI: ``nbodykit-tpu-serve --trace 100`` (or
``python -m nbodykit_tpu.serve``).  Guide: docs/SERVING.md.
"""

from .request import ALGORITHMS, AnalysisRequest  # noqa: F401
from .admission import (ADMIT, DEGRADE, REJECT,  # noqa: F401
                        AdmissionDecision, admit)
from .scheduler import ProgramCache, program_label  # noqa: F401
from .batching import BatchPolicy  # noqa: F401
from .server import (COMPLETED, EVICTED, FAILED,  # noqa: F401
                     REJECTED, AnalysisServer, RequestResult)
from .synth import (generate_region_trace, generate_trace,  # noqa: F401
                    replay, replay_region)
from .region import (DEFAULT_CLASSES, Fleet, QoSPolicy,  # noqa: F401
                     Region, RegionRouter, ResultCache,
                     ServiceClass, result_key)
