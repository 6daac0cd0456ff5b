"""nbodykit_tpu.tune — measured autotuning with a persistent
per-platform performance database.

Round 5's verdict made the case: every kernel/knob choice in the
stack (``paint_method``, ``paint_chunk_size``, ``fft_chunk_bytes``,
mxu order/deposit engines, exchange slack) was a static guess, and
the hand-picked flagship MXU paint **lost to the plain scatter on
real hardware at every measured scale**.  The reference gets away
with fixed C kernels; a TPU-native stack cannot — the winning kernel
flips with mesh size, particle density and backend (the regime
dependence the mass-assignment literature predicts for deposit cost:
Jing 2005; Cui et al. 2008, PAPERS.md).  So choices are now
*measured*, cached, and carried between runs:

- :mod:`.space` — declarative search spaces per op (paint kernel ×
  chunk size × order/deposit engine; FFT chunk bytes; exchange
  slack), with deterministic candidate plans;
- :mod:`.trial` — warmup + timed reps per candidate under the
  resilience :class:`~nbodykit_tpu.resilience.Supervisor`, so a
  device loss or HBM OOM marks the *candidate* infeasible instead
  of killing the tune run; every trial is a ``tune.*`` span +
  counter;
- :mod:`.cache` — the persistent, content-keyed database
  (``TUNE_CACHE.json``, atomic tmp+rename), keyed by (platform,
  device kind, device count, op, shape class, dtype), with
  nearest-shape-class fallback and staleness stamps;
- :mod:`.resolve` — dispatch-time resolution:
  ``set_options(paint_method='auto')`` / ``fft_chunk_bytes='auto'``
  consult the cache; a cold cache falls back to today's defaults
  with **zero trial overhead** (trials only ever run offline, via
  ``nbodykit-tpu-tune`` / ``python -m nbodykit_tpu.tune``).

Cache location: the ``tune_cache`` option (seeded from
``$NBKIT_TUNE_CACHE``), defaulting to the committed repo-root
``TUNE_CACHE.json``.  Doctor posture: the ``tune`` verdict line WARNs
on entries measured on a different platform/device kind than the
current one or older than 30 days.  Full guide: docs/TUNE.md.
"""

from .cache import (STALE_DAYS, TUNABLE_OPTIONS, TuneCache,  # noqa: F401
                    cache_path, cache_summary, canonical_dtype,
                    class_coords, class_distance, default_cache_path,
                    device_signature, entry_age_days, entry_key,
                    make_key, reset_cache_memo, shape_class,
                    validate_cache)
from .space import (Candidate, SearchSpace, default_spaces,  # noqa: F401
                    exchange_space, fft_space, paint_space)
from .trial import plan_spaces, run_space  # noqa: F401
from .resolve import (FALLBACKS, effective_int_option,  # noqa: F401
                      resolve_exchange_slack, resolve_fft_chunk_bytes,
                      resolve_paint, resolve_paint_deposit,
                      tuned_snapshot)
