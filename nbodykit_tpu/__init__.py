"""nbodykit-tpu: a TPU-native large-scale-structure analysis framework.

A ground-up re-design of the capabilities of bccp/nbodykit (reference:
/root/reference) for the JAX/XLA/TPU stack:

- distributed particle catalogs and 3-D density meshes are global
  ``jax.Array``s sharded over a ``jax.sharding.Mesh`` (slab decomposition),
  not MPI-rank-local numpy arrays;
- the distributed FFT (reference: pfft/pmesh) is local FFTs + in-graph
  ``lax.all_to_all`` transposes under ``jax.shard_map``;
- particle painting/readout (reference: pmesh C kernels) are fused
  scatter/gather kernels with halo exchange via ``lax.ppermute``;
- MPI collectives (reference: mpi4py) become XLA collectives inside jit;
- random numbers are device-count invariant by construction: every random
  draw is a function of (seed, global index) generated as a global sharded
  array (reference achieves this with MPIRandomState chunked seeding,
  nbodykit/mpirng.py:5).

The public API mirrors the capability surface inventoried in SURVEY.md §2:
catalogs, meshes, FFT-based spectra estimators, group finders, pair counting,
mock generation, cosmology, IO, and batch processing.
"""

import logging
import os
import time
from contextlib import contextmanager

__version__ = "0.1.0"

# ---------------------------------------------------------------------------
# global options (reference: nbodykit/__init__.py:22-25, set_options :215-256)
# ---------------------------------------------------------------------------

# the one table of defaults: every read site takes the option as it
# stands here. 'auto' is a value only where the code decides from
# something it observes (paint_order: the backend; ingest_cache_bytes:
# memory_plan; data_steal_grace_s: the environment)
_default_options = {
    # dtype used for meshes created via to_mesh() unless overridden.
    # 'bf16' stores mesh buffers in bfloat16 (half the HBM of 'f4')
    # with f32-compensated deposit merges and immediate re-widening on
    # readout/FFT entry (docs/PERF.md "Halving the bytes")
    'mesh_dtype': 'f4',
    # all_to_all payload compression for the distributed FFT
    # (parallel/dfft.py, slab AND pencil drivers): 'none' sends the
    # f32 complex shards as-is; 'bf16' casts the payload
    # bfloat16-on-the-wire and re-widens to f32 immediately after the
    # collective; 'int16' sends an int16-quantized payload with
    # per-slab f32 scale factors carried alongside the shards. FFT
    # stages always COMPUTE f32 — only the wire bytes halve
    'a2a_compress': 'none',
    # number of particles painted per chunk on the host-streaming path
    'paint_chunk_size': 1024 * 1024 * 16,
    # default resampler window
    'resampler': 'cic',
    # paint kernel: 'mxu' (the tile deposit: one payload-carrying
    # sort, contiguous bucket slices, per-tile matrix products at f32
    # grade; on a block too small for its tiles, the scatter),
    # 'scatter' (chunked scatter-add, what jax.grad runs), 'sort'
    # (scatter-free sort + segmented reduction), 'segsum' or
    # 'streams'; see ops/paint.py and PERF.md section 6, PR 33. The
    # last three have no chip row
    'paint_method': 'mxu',
    # stable ordering engine of the 'segsum' paint: 'auto' (radix
    # counting sort on TPU, bitonic argsort elsewhere), 'argsort', or
    # 'radix' (ops/radix.py)
    'paint_order': 'auto',
    # deposit engine for the mxu paint: 'xla' (one-hot expansions via
    # XLA) or 'pallas' (fused VMEM kernel, ops/paint_pallas.py)
    'paint_deposit': 'xla',
    # replica-mesh count for the 'streams' paint kernel (the number of
    # independent scatter chains; each replica is a full mesh buffer —
    # memory_plan counts them against the HBM budget)
    'paint_streams': 4,
    # single-device FFTs whose complex output exceeds this many bytes
    # run as slab-chunked per-axis passes (a single FFT op over a
    # multi-GB buffer exceeds TPU compiler limits; see parallel/dfft).
    # 0 disables chunking.
    'fft_chunk_bytes': 2 ** 31,
    # distributed-FFT decomposition: 'slab' (1-D mesh, one P-way
    # all_to_all) or 'pencil' (2-D Mesh(('x','y')), two smaller
    # transposes — inner over ICI, outer over DCN; parallel/dfft.py)
    'fft_decomp': 'slab',
    # explicit (Px, Py) factorization for the pencil path, as 'PXxPY'
    # (e.g. '4x2') or a tuple; None picks the most nearly square
    # factorization of the device count (runtime.default_pencil_factor)
    'fft_pencil': None,
    # rows per host chunk on the streaming ingestion path
    # (nbodykit_tpu.ingest, docs/INGEST.md): the window each
    # double-buffered device_put/paint step moves — the host never
    # holds more than two windows
    'ingest_chunk_rows': 262144,
    # overlap H2D transfer of chunk i+1 with the paint of chunk i
    # (the double buffer). False serializes transfer-then-paint —
    # kept selectable for A/B measurement (bench --ingest)
    'ingest_overlap': True,
    # hard cap (bytes) on the on-device catalog cache per sub-mesh;
    # 'auto'/None defers entirely to memory_plan pricing at admission
    'ingest_cache_bytes': 'auto',
    # telemetry sink: None disables; a path enables the span tracer +
    # crash-safe JSONL trace (nbodykit_tpu.diagnostics, docs/
    # OBSERVABILITY.md). Seeded from $NBKIT_DIAGNOSTICS so detached
    # workers (bench, multi-host) can be told to leave a post-mortem
    # trace without code changes.
    'diagnostics': os.environ.get('NBKIT_DIAGNOSTICS') or None,
    # deterministic fault injection (nbodykit_tpu.resilience.faults,
    # docs/RESILIENCE.md): 'point@N:action[,...]' fires a chosen
    # XlaRuntimeError (or SIGKILL) at the Nth call to a named fault
    # point. None disables. Seeded from $NBKIT_FAULTS so detached
    # workers (bench, multi-host) can be fault-injected without code
    # changes.
    'faults': os.environ.get('NBKIT_FAULTS') or None,
    # silent-data-corruption defense tier (nbodykit_tpu.resilience.
    # integrity, docs/INTEGRITY.md): 'off' (default — bit-identical to
    # a build without the integrity layer, zero added ops) or 'cheap'
    # (on-device invariants priced as near-free reductions: paint mass
    # conservation, Parseval brackets around the distributed FFTs,
    # NaN/Inf tripwires, fold-reduction checksums across every
    # all_to_all wire format). Seeded from $NBKIT_INTEGRITY so
    # detached workers can be armed without code changes.
    'integrity': os.environ.get('NBKIT_INTEGRITY') or 'off',
    # verify the per-physical-file byte-sum checksums bigfile columns
    # are written with on first read (io/bigfile.py); a mismatch
    # raises a structured ChecksumMismatch instead of silently
    # analyzing corrupt rows. False skips verification (bulk loads
    # where the caller audits out of band).
    'io_verify_checksums': True,
    # seconds a data_ref request is reserved for its cache-affine
    # serve worker before any idle worker may steal it (a steal pays
    # a cold re-ingest; docs/SERVING.md). 'auto' defers to
    # $NBKIT_DATA_STEAL_GRACE_S, else the AnalysisServer default
    # (1.0). Must be a non-negative finite number; 0 steals freely.
    # Resolved at server construction, validated there.
    'data_steal_grace_s': 'auto',
    # bispectrum estimator: 'fft' (Scoccimarro filtered-field
    # triangle counts, low k) or 'direct' (blocked pairwise mode sums
    # on the MXU, high k; catalog sources only)
    'bspec_method': 'fft',
    # tile edge of the direct path's dense (tile x tile) phase blocks
    # (ops/pairblock.py)
    'pairblock_tile': 1024,
    # live telemetry export (nbodykit_tpu.diagnostics.export,
    # docs/OBSERVABILITY.md): an integer TCP port starts a
    # zero-dependency background HTTP thread serving the metrics
    # registry and SLO state as Prometheus text (/metrics), JSON
    # snapshots (/metrics.json, /slo) and the flight-recorder ring
    # (/flight). 0 binds an ephemeral port (the exporter reports the
    # real one); None disables. Seeded from $NBKIT_TELEMETRY_PORT so
    # detached workers can be scraped without code changes.
    'telemetry_port': os.environ.get('NBKIT_TELEMETRY_PORT') or None,
}


class _Options(object):
    """Thread-aware options mapping.

    The main thread reads/writes one shared dict; any other thread
    (e.g. a TaskManager worker farming tasks to device sub-meshes,
    batch.py) gets its own copy seeded from the main thread's values at
    first use — so concurrent tasks using ``set_options`` cannot race
    each other or corrupt the process-wide defaults.
    """

    def __init__(self, defaults):
        import threading
        self._threading = threading
        self._main = dict(defaults)
        self._tls = threading.local()

    def _cur(self):
        if self._threading.current_thread() is \
                self._threading.main_thread():
            return self._main
        d = getattr(self._tls, 'd', None)
        if d is None:
            d = dict(self._main)
            self._tls.d = d
        return d

    def __getitem__(self, key):
        return self._cur()[key]

    def __setitem__(self, key, value):
        self._cur()[key] = value

    def __contains__(self, key):
        return key in self._cur()

    def __iter__(self):
        return iter(self._cur())

    def keys(self):
        return self._cur().keys()

    def copy(self):
        return dict(self._cur())

    def update(self, other):
        self._cur().update(other)

    def clear(self):
        self._cur().clear()


_global_options = _Options(_default_options)

# kernel choices are constants: for these the read sites take the
# value as it stands, and nothing is left to answer an 'auto'
_NO_AUTO = ('mesh_dtype', 'a2a_compress', 'paint_method',
            'paint_chunk_size', 'paint_deposit', 'paint_streams',
            'fft_chunk_bytes', 'fft_decomp', 'ingest_chunk_rows',
            'bspec_method', 'pairblock_tile')


def _check_options(kwargs):
    """Refuse what no read site could take: an unknown option, or
    ``'auto'`` for an option the code does not decide itself."""
    for key, value in kwargs.items():
        if key not in _global_options:
            raise KeyError('invalid option: %r (valid: %s)'
                           % (key, sorted(_global_options)))
        if key in _NO_AUTO and isinstance(value, str) \
                and value == 'auto':
            raise ValueError(
                "%s='auto' is not a value: the choice is a constant, "
                "%r by default; pass that or another concrete value"
                % (key, _default_options[key]))


class set_options(object):
    """Context manager / callable to set global framework options.

    Mirrors the semantics of the reference's ``nbodykit.set_options``
    (nbodykit/__init__.py:215-256): usable both as a plain call and as a
    ``with`` block that restores the previous values on exit.

    Parameters
    ----------
    mesh_dtype : str
        default dtype of meshes created by ``to_mesh``: 'f4' (the
        default), 'f8' (demoted to f4 when x64 is off), 'bf16' (mesh
        buffers stored bfloat16 at half the f4 HBM footprint — paint
        deposits into bf16 replica meshes with an f32 compensated
        two-sum merge, readout and FFT entry re-widen to f32
        immediately; accuracy budget asserted in tests/
        test_precision.py).
    a2a_compress : str
        distributed-FFT ``all_to_all`` payload compression
        (parallel/dfft.py, both slab and pencil): 'none' (default),
        'bf16' (bfloat16 on the wire, f32 out — the payload is
        re-widened immediately after the collective) or 'int16'
        (quantized payload + per-slab f32 scale factors riding
        alongside).  FFT butterflies always compute f32; only the wire
        bytes halve.
    paint_chunk_size : int
        number of particles processed per chunk when streaming from host.
    resampler : str
        default window: 'nnb', 'cic', 'tsc', 'pcs'.
    paint_method : str
        'mxu' (the default: the tile deposit, the scatter where the
        block is too small for its tiles), 'scatter', 'sort',
        'segsum', 'streams' — the local deposit kernel.
    paint_streams : int
        replica-mesh count for the 'streams' paint kernel — the number
        of independent scatter chains the s^3 window-offset streams
        are dealt onto (each replica is a full mesh buffer, counted by
        ``memory_plan``); 4 by default.
    fft_chunk_bytes : int
        single-device FFTs with complex output larger than this run as
        slab-chunked per-axis passes (0 disables); 2**31 by default.
    fft_decomp : str
        distributed-FFT decomposition: 'slab' (the default: one P-way
        all_to_all over the 1-D mesh) or 'pencil' (two smaller
        transposes over a 2-D ``Mesh(('x','y'))`` — see
        parallel/dfft.py and docs/PERF.md "Slab vs pencil").
    fft_pencil : str, tuple or None
        explicit (Px, Py) device factorization for the pencil path
        ('4x2' or ``(4, 2)``); None picks the most nearly square
        factorization of the device count.
    diagnostics : str or None
        path of the telemetry sink (a directory, or a ``*.jsonl``
        file): enables the span tracer + metrics of
        :mod:`nbodykit_tpu.diagnostics` with crash-safe JSONL output.
        None (the default) disables all tracing at zero cost.
    faults : str or None
        deterministic fault-injection spec
        (``'point@N:action[,...]'``) for
        :mod:`nbodykit_tpu.resilience.faults`; actions are
        ``unavailable`` / ``resource_exhausted`` / ``deadline`` /
        ``internal`` / ``kill`` / ``corrupt[:bits]`` (flip payload
        bits at a named data-injection point — the testable stand-in
        for real silent data corruption).  None (the default)
        disables.
    integrity : str
        silent-data-corruption defense
        (:mod:`nbodykit_tpu.resilience.integrity`, docs/INTEGRITY.md):
        'off' (the default — bit-identical results and zero added
        ops) or 'cheap' (tier-0 on-device invariants: exact paint
        mass conservation, Parseval checks bracketing the distributed
        FFTs, NaN/Inf tripwires on mesh-sized intermediates, and
        fold-reduction checksums across every ``all_to_all`` payload
        including the bf16/int16 compressed wire formats).  A
        violation raises a classified
        :class:`~nbodykit_tpu.resilience.IntegrityError`; the
        Supervisor retries it exactly once.
    io_verify_checksums : bool
        verify each bigfile physical file's stored 32-bit byte-sum
        checksum the first time the file is read
        (:mod:`nbodykit_tpu.io.bigfile`); a mismatch raises
        :class:`~nbodykit_tpu.io.bigfile.ChecksumMismatch` with the
        file, column and both sums.  True by default; False opts out.
    data_steal_grace_s : float or 'auto'
        seconds a ``data_ref`` request stays reserved for its
        cache-affine serve worker before any idle worker may steal it
        (stealing pays a cold catalog re-ingest; docs/SERVING.md).
        'auto' (the default) defers to ``$NBKIT_DATA_STEAL_GRACE_S``,
        else 1.0.  Must be non-negative and finite (0 disables the
        grace window entirely); validated when an
        :class:`~nbodykit_tpu.serve.AnalysisServer` is constructed.
    telemetry_port : int or None
        TCP port for the live telemetry exporter
        (:mod:`nbodykit_tpu.diagnostics.export`): a background HTTP
        thread serving the metrics registry as Prometheus text
        (``/metrics``), JSON snapshots (``/metrics.json``, ``/slo``)
        and the flight-recorder ring (``/flight``).  0 binds an
        ephemeral port; None (the default) disables.  Seeded from
        ``$NBKIT_TELEMETRY_PORT``.  The serve/region front doors
        start the exporter on construction when this is set.
    """

    def __init__(self, **kwargs):
        self.old = _global_options.copy()
        _check_options(kwargs)
        _global_options.update(kwargs)

    def __enter__(self):
        return self

    def __exit__(self, *args):
        _global_options.clear()
        _global_options.update(self.old)


@contextmanager
def option_scope(**overrides):
    """Request-scoped option override that CANNOT leak.

    ``set_options`` used as a context manager restores the values it
    saved — but a bare ``set_options(...)`` call inside the block (or
    inside library code the block runs) survives it.  On the main
    thread that is a deliberate feature; on a long-lived worker thread
    that is a cross-tenant leak: ``_Options`` gives every non-main
    thread a persistent thread-local dict, so whatever request N
    leaves behind becomes request N+1's ambient configuration when the
    pool reuses the thread.

    This context snapshots the calling thread's FULL option dict on
    entry and restores it wholesale on exit, so nothing set inside the
    scope — by ``overrides``, by nested ``set_options``, by a
    degradation-ladder rung — outlives it.  The serving layer
    (:mod:`nbodykit_tpu.serve`) wraps every request in one.
    """
    _check_options(overrides)
    saved = _global_options.copy()
    _global_options.update(overrides)
    try:
        yield
    finally:
        _global_options.clear()
        _global_options.update(saved)


# ---------------------------------------------------------------------------
# logging (reference: nbodykit/__init__.py:258-300)
# ---------------------------------------------------------------------------

_logging_handler = None


def setup_logging(log_level="info"):
    """Set up logging with elapsed-wall-clock-stamped records.

    The reference formats records as ``[ elapsed ] rank: msg``
    (nbodykit/__init__.py:269-300); here there is a single controller
    process, so records are ``[ elapsed ] level: msg``.
    """
    levels = {
        "info": logging.INFO,
        "debug": logging.DEBUG,
        "warning": logging.WARNING,
        "error": logging.ERROR,
    }

    logger = logging.getLogger()
    t0 = time.time()

    class Formatter(logging.Formatter):
        def format(self, record):
            s1 = ('[ %09.2f ] ' % (time.time() - t0))
            return s1 + logging.Formatter.format(self, record)

    fmt = Formatter(fmt='%(levelname)s %(name)s: %(message)s')

    global _logging_handler
    if _logging_handler is None:
        _logging_handler = logging.StreamHandler()
        logger.addHandler(_logging_handler)

    _logging_handler.setFormatter(fmt)
    logger.setLevel(levels[log_level])


@contextmanager
def timer(name, logger=None):
    """Context manager timing a named phase (reference: utils.timer,
    nbodykit/utils.py:491).

    Routed through :mod:`nbodykit_tpu.diagnostics`: when the
    ``diagnostics`` option is set, every existing ``timer(...)`` call
    site also emits a crash-safe ``timer.<name>`` span with zero
    caller changes (no-op otherwise)."""
    from .diagnostics import span
    t0 = time.time()
    with span('timer.%s' % name):
        yield
    dt = time.time() - t0
    msg = "%s: %.3f s" % (name, dt)
    if logger is not None:
        logger.info(msg)
    else:
        logging.getLogger('timer').info(msg)


from .parallel.runtime import CurrentMesh, use_mesh, cpu_mesh, tpu_mesh  # noqa: E402,F401


@contextmanager
def profile(path='/tmp/nbodykit-tpu-trace', host=False):
    """Capture a jax profiler trace of the enclosed block (SURVEY.md §5
    'tracing': the reference has wall-clock phase logging only; here the
    full XLA timeline lands in TensorBoard format at ``path``).

    Also emits a ``profile`` span (with the trace path) when the
    ``diagnostics`` option is set, so the XLA capture window is
    locatable inside the span timeline."""
    import jax
    from .diagnostics import span
    jax.profiler.start_trace(path)
    try:
        with span('profile', path=path, host=bool(host)):
            yield path
    finally:
        jax.profiler.stop_trace()
