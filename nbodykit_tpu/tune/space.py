"""Declarative search spaces: which knob settings compete, per op.

A :class:`SearchSpace` names an op (the cache key's ``op`` field), the
options its winner provides, a deterministic candidate list for a
trial context, and a runner factory that builds the measured callable.
The candidate list is a pure function of the context — no RNG, no
clock — so a trial *plan* is reproducible byte-for-byte and can be
printed (``nbodykit-tpu-tune --dry-run``) without touching a device.

The spaces below cover the knobs round 5 proved are regime-dependent
guesses (VERDICT.md: the hand-picked MXU paint lost to the plain
scatter on real hardware at every measured scale):

- **paint** — kernel (``scatter`` / ``sort`` / ``segsum`` /
  ``streams`` / ``mxu``) × scatter chunk size × one-sort ordering
  engine (``radix`` vs ``argsort``, segsum and mxu) × stream count
  (``streams``: k ∈ {2, 4, 8}, each admitted only if
  ``pmesh.memory_plan`` keeps its k replica meshes inside the
  0.85×HBM budget at the trial shape) × mxu deposit engine (``xla``
  vs ``pallas`` — MXU backends only) × mesh storage dtype (``mesh_dtype``:
  ``f4`` vs ``bf16`` half-storage with two-sum compensated merges —
  ISSUE 13, accuracy-gated by tests/test_precision.py);
- **fft** — the single-device ``fft_chunk_bytes`` dispatch target
  (one-shot in-jit vs slab-chunked vs eager lowmem), and on
  multi-device contexts the ``fft_decomp`` knob (slab's one P-way
  all_to_all vs the pencil path's two smaller transposes over a 2-D
  mesh); fft entries are keyed by the (Px, Py) factorization the
  pencil candidate runs with, so a winner measured on 4x2 never
  answers an 8x1 question;
- **exchange** — the counted-capacity slack of the particle
  ``all_to_all`` (multi-device contexts only).
"""

from .cache import shape_class


class Candidate(object):
    """One competitor: a name plus the ``set_options`` overrides that
    select it."""

    def __init__(self, name, options):
        self.name = str(name)
        self.options = dict(options)

    def __repr__(self):
        return 'Candidate(%r, %r)' % (self.name, self.options)


class SearchSpace(object):
    """Competing configurations of one op.

    Parameters
    ----------
    op : str — cache-key op name ('paint', 'fft', 'exchange').
    provides : tuple of option names the winner carries into the cache
        (a winner never writes options its trials did not vary).
    candidates : callable(ctx) -> list of :class:`Candidate`, pure in
        ctx.
    make_runner : callable(ctx) -> zero-arg callable running + syncing
        one trial iteration.  Called *inside* each candidate's
        ``set_options`` block, so option reads inside the runner see
        the candidate's values.
    """

    def __init__(self, op, provides, candidates, make_runner):
        self.op = str(op)
        self.provides = tuple(provides)
        self._candidates = candidates
        self.make_runner = make_runner

    def candidates(self, ctx):
        return list(self._candidates(ctx))

    def shape_class(self, ctx):
        # a ctx carrying 'mesh_shape' (the (Px, Py) factorization its
        # trials run with — the fft space on a multi-device mesh) keys
        # its entry under that factorization: decomp winners must not
        # travel across device-mesh shapes (cache.class_distance)
        return shape_class(nmesh=ctx.get('nmesh'),
                           npart=ctx.get('npart'),
                           mesh_shape=ctx.get('mesh_shape'))


def _sync(out):
    """Wait for ``out`` (``block_until_ready`` waits on the attached
    chip: chip_smoke.py's device phase, PR 22)."""
    import jax
    return jax.block_until_ready(out)


def _trial_positions(ctx):
    """Deterministic uniform positions for a trial (seeded from ctx;
    the plan stays reproducible)."""
    import jax
    import jax.numpy as jnp
    from ..parallel.runtime import CurrentMesh, shard_leading
    box = float(ctx.get('box', 1000.0))
    pos = jax.random.uniform(jax.random.key(int(ctx.get('seed', 7))),
                             (int(ctx['npart']), 3), jnp.float32,
                             0.0, box)
    mesh = CurrentMesh.resolve(None)
    if mesh is not None:
        pos = shard_leading(mesh, pos)
    _sync(pos)
    return pos


# ---------------------------------------------------------------------------
# paint

def _paint_candidates(ctx):
    from ..utils import is_mxu_backend
    chunk = 1024 * 1024 * 16
    cands = [
        Candidate('scatter', {'paint_method': 'scatter'}),
        Candidate('scatter-chunk4m', {'paint_method': 'scatter',
                                      'paint_chunk_size':
                                      1024 * 1024 * 4}),
        Candidate('sort', {'paint_method': 'sort'}),
        Candidate('segsum-argsort', {'paint_method': 'segsum',
                                     'paint_order': 'argsort'}),
        Candidate('segsum-radix', {'paint_method': 'segsum',
                                   'paint_order': 'radix'}),
    ]
    # offset-stream scatter: k replica meshes are k full mesh units of
    # HBM, so each stream count must prove through memory_plan that
    # the pipeline still fits the device the trial runs on before it
    # may compete.  The tuner owns that device: its memory is read
    # here once and the plans below are arithmetic.
    import jax
    from ..pmesh import device_hbm_bytes, memory_plan
    hbm_bytes = device_hbm_bytes(jax.devices()[0])
    for k in (2, 4, 8):
        plan = memory_plan(int(ctx['nmesh']), int(ctx['npart']),
                           dtype=ctx.get('dtype', 'f4'),
                           paint_method='streams', paint_streams=k,
                           hbm_bytes=hbm_bytes)
        if plan['fits']:
            cands.append(Candidate('streams%d' % k,
                                   {'paint_method': 'streams',
                                    'paint_streams': k}))
    cands.extend([
        Candidate('mxu-argsort-xla', {'paint_method': 'mxu',
                                      'paint_order': 'argsort',
                                      'paint_deposit': 'xla'}),
        Candidate('mxu-radix-xla', {'paint_method': 'mxu',
                                    'paint_order': 'radix',
                                    'paint_deposit': 'xla'}),
    ])
    # half-storage mesh candidates (ISSUE 13): bf16 replica/field
    # buffers halve the HBM traffic of the scatter-bound paint; the
    # two-sum merge keeps the accuracy inside the tests/test_precision
    # budget, and memory_plan prices the halved meshes so streams
    # counts that only fit at 2 bytes/cell may compete here too
    cands.append(Candidate('scatter-bf16', {'paint_method': 'scatter',
                                            'mesh_dtype': 'bf16'}))
    for k in (4, 8):
        plan = memory_plan(int(ctx['nmesh']), int(ctx['npart']),
                           dtype='bf16', paint_method='streams',
                           paint_streams=k, hbm_bytes=hbm_bytes)
        if plan['fits']:
            cands.append(Candidate('streams%d-bf16' % k,
                                   {'paint_method': 'streams',
                                    'paint_streams': k,
                                    'mesh_dtype': 'bf16'}))
    for c in cands:
        c.options.setdefault('paint_chunk_size', chunk)
        # cold default = today's behavior: every candidate that did
        # not ask for bf16 races (and would win as) full-width f4
        c.options.setdefault('mesh_dtype', 'f4')
    if is_mxu_backend():
        # the Pallas VMEM deposit is interpreted (≈100x slow) off-MXU:
        # off-chip it would only ever lose, so it competes on a TPU
        # only.  There it compiles (tests/test_tpu_compile.py holds
        # the kernel at its 512^3 / 1e7 shapes), and a compiler error
        # is the trial's error, not a reason to drop the candidate
        cands.append(Candidate('mxu-radix-pallas',
                               {'paint_method': 'mxu',
                                'paint_order': 'radix',
                                'paint_deposit': 'pallas',
                                'paint_chunk_size': chunk}))
    return cands


def registered_paint_candidates(nmesh, npart, dtype='f4'):
    """The paint candidate list for a shape, as the tuner would build
    it — the enumeration bench.py ``--paint-all``, the smoke gate and
    tests/test_paint_kernels.py iterate so 'every registered
    candidate' means exactly the competitors of a real trial."""
    return _paint_candidates({'nmesh': int(nmesh), 'npart': int(npart),
                              'dtype': dtype})


def _paint_runner(ctx):
    from .. import _global_options
    from ..pmesh import ParticleMesh
    # built inside the candidate's set_options block: a mesh_dtype
    # the candidate carries (e.g. 'bf16') overrides the ctx dtype so
    # the trial actually runs the half-storage pipeline
    mdt = _global_options['mesh_dtype']
    dtype = ctx.get('dtype', 'f4') if mdt in (None, 'auto') else mdt
    pm = ParticleMesh(Nmesh=int(ctx['nmesh']),
                      BoxSize=float(ctx.get('box', 1000.0)),
                      dtype=dtype)
    pos = _trial_positions(ctx)
    resampler = ctx.get('resampler', 'cic')

    def once():
        return _sync(pm.paint(pos, 1.0, resampler=resampler))
    return once


def paint_space():
    return SearchSpace('paint',
                       ('paint_method', 'paint_order', 'paint_deposit',
                        'paint_chunk_size', 'paint_streams',
                        'mesh_dtype'),
                       _paint_candidates, _paint_runner)


# ---------------------------------------------------------------------------
# fft

def _fft_candidates(ctx):
    # the real dispatch ladder: one-shot in-jit, then ever-smaller
    # slab-chunked / lowmem passes (parallel/dfft.py)
    cands = [Candidate('chunk2g', {'fft_chunk_bytes': 2 ** 31}),
             Candidate('chunk256m', {'fft_chunk_bytes': 2 ** 28}),
             Candidate('chunk64m', {'fft_chunk_bytes': 2 ** 26})]
    for c in cands:
        c.options.setdefault('fft_decomp', 'slab')
    # multi-device contexts also race the decomposition itself: the
    # pencil path (two smaller transposes over a 2-D mesh) vs slab's
    # one P-way all_to_all. The factorization comes from the ctx (the
    # CLI stamps the one the transform would run with) so the entry's
    # shape class — and therefore the winner's reach — carries it.
    # The a2a wire format races alongside (a2a_compress).
    nproc = int(ctx.get('nproc', 1))
    if nproc > 1:
        # compressed-wire candidates (ISSUE 13): the transposes are
        # THE slab/pencil cost, so the a2a payload format races too —
        # bf16 planes (half the bytes, re-widened on receipt) and
        # int16 quantized planes with per-shard scales.  Single-device
        # contexts have no collective, so the knob never races there.
        cands.append(Candidate('slab-a2a-bf16',
                               {'fft_decomp': 'slab',
                                'fft_chunk_bytes': 2 ** 31,
                                'a2a_compress': 'bf16'}))
        cands.append(Candidate('slab-a2a-int16',
                               {'fft_decomp': 'slab',
                                'fft_chunk_bytes': 2 ** 31,
                                'a2a_compress': 'int16'}))
    if nproc > 1 and ctx.get('mesh_shape'):
        px, py = ctx['mesh_shape']
        cands.append(Candidate(
            'pencil%dx%d' % (px, py),
            {'fft_decomp': 'pencil', 'fft_pencil': '%dx%d' % (px, py),
             'fft_chunk_bytes': 2 ** 31}))
        cands.append(Candidate(
            'pencil%dx%d-a2a-bf16' % (px, py),
            {'fft_decomp': 'pencil', 'fft_pencil': '%dx%d' % (px, py),
             'fft_chunk_bytes': 2 ** 31, 'a2a_compress': 'bf16'}))
    for c in cands:
        # cold default = today's behavior: uncompressed payloads
        c.options.setdefault('a2a_compress', 'none')
    return cands


def _fft_runner(ctx):
    import jax
    import jax.numpy as jnp
    from ..pmesh import ParticleMesh
    pm = ParticleMesh(Nmesh=int(ctx['nmesh']),
                      BoxSize=float(ctx.get('box', 1000.0)),
                      dtype=ctx.get('dtype', 'f4'))
    x = jax.random.uniform(jax.random.key(int(ctx.get('seed', 7))),
                           pm.shape_real, jnp.float32)
    x = jnp.asarray(x, pm.dtype)
    if pm.comm is not None:
        x = jax.device_put(x, pm.sharding())
    _sync(x)

    def once():
        return _sync(pm.r2c(x))
    return once


def fft_space():
    return SearchSpace('fft',
                       ('fft_chunk_bytes', 'fft_decomp', 'fft_pencil',
                        'a2a_compress'),
                       _fft_candidates, _fft_runner)


# ---------------------------------------------------------------------------
# exchange

def _exchange_candidates(ctx):
    return [Candidate('slack1.05', {'exchange_slack': 1.05}),
            Candidate('slack1.25', {'exchange_slack': 1.25}),
            Candidate('slack2.0', {'exchange_slack': 2.0})]


def _exchange_runner(ctx):
    from .. import _global_options
    from ..parallel.exchange import auto_capacity, exchange_by_dest
    from ..parallel.runtime import CurrentMesh, mesh_size
    mesh = CurrentMesh.resolve(None)
    nproc = mesh_size(mesh)
    if nproc <= 1:
        raise ValueError('exchange tuning needs a multi-device mesh '
                         '(nproc=%d)' % nproc)
    import jax
    import jax.numpy as jnp
    from ..parallel.runtime import shard_leading
    n = int(ctx['npart'])
    key = jax.random.key(int(ctx.get('seed', 7)))
    dest = shard_leading(mesh, jax.random.randint(
        key, (n,), 0, nproc, jnp.int32))
    vals = shard_leading(mesh, jax.random.uniform(
        key, (n,), jnp.float32))
    _sync((dest, vals))
    # the candidate's slack sizes the static per-pair buffers — read
    # at runner-build time, inside the candidate's set_options block
    cap = auto_capacity(dest, nproc,
                        slack=float(_global_options['exchange_slack']))

    def once():
        recv, valid, dropped = exchange_by_dest(dest, [vals], mesh, cap)
        return _sync((recv[0], dropped))
    return once


def exchange_space():
    return SearchSpace('exchange', ('exchange_slack',),
                       _exchange_candidates, _exchange_runner)


# ---------------------------------------------------------------------------
# ingest

def _ingest_candidates(ctx):
    # the chunk-rows ladder: windows small enough to keep two host
    # buffers tiny, large enough to amortize per-chunk dispatch.  The
    # ladder is clipped to the trial's particle count (a window larger
    # than the catalog degenerates to whole-load and measures nothing),
    # keyed by the part-count shape class so a 1e6-row winner never
    # answers a 1e9-row question.
    npart = int(ctx['npart'])
    cands = []
    for rows in (32768, 65536, 131072, 262144, 524288, 1048576):
        if rows >= 2 * npart and cands:
            break
        cands.append(Candidate('rows%dk' % (rows // 1024),
                               {'ingest_chunk_rows': rows}))
    return cands


def _ingest_runner(ctx):
    # stream a deterministic in-memory catalog (the same rows every
    # candidate) through the full chunk pipeline — rule-tree sharding,
    # padded device_put, overlapped paint — on the current mesh; the
    # candidate's ingest_chunk_rows is read inside ingest_catalog
    import numpy as np

    from ..ingest.stream import ArraySource, ingest_catalog
    from ..pmesh import ParticleMesh
    box = float(ctx.get('box', 1000.0))
    rng = np.random.RandomState(int(ctx.get('seed', 7)))
    pos = rng.uniform(0.0, box, size=(int(ctx['npart']), 3)) \
        .astype('f4')
    src = ArraySource({'Position': pos})
    from ..parallel.runtime import CurrentMesh
    pm = ParticleMesh(Nmesh=int(ctx.get('nmesh', 64)), BoxSize=box,
                      dtype=ctx.get('dtype', 'f4'),
                      comm=CurrentMesh.resolve(None))

    def once():
        field, _, _ = ingest_catalog(src, pm)
        return _sync(field)
    return once


def ingest_space():
    return SearchSpace('ingest', ('ingest_chunk_rows',),
                       _ingest_candidates, _ingest_runner)


# ---------------------------------------------------------------------------
# bspec — the FFT/direct bispectrum crossover (ISSUE 20)

def _bspec_candidates(ctx):
    """The estimator race: the Scoccimarro FFT path against the
    MXU-shaped direct path at several dense-block tiles.  Which wins
    is a *per-platform, per-shape* property — the direct path's
    O(Npart x Nk) FLOPs beat the FFT's wire time only where the MXU
    can stream them (PAPERS.md 2005.01739) — so the crossover is
    measured here, never guessed.  Direct tiles are clipped to the
    trial's particle count (a tile bigger than the catalog pads to
    waste and measures nothing)."""
    npart = int(ctx['npart'])
    cands = [Candidate('fft', {'bspec_method': 'fft'})]
    for tile in (256, 1024, 4096):
        if tile >= 4 * npart and len(cands) > 1:
            break
        cands.append(Candidate('direct-tile%d' % tile,
                               {'bspec_method': 'direct',
                                'pairblock_tile': tile}))
    return cands


def _bspec_runner(ctx):
    """One bounded bispectrum measurement per candidate: same
    deterministic uniform catalog, same shell count; the candidate's
    ``bspec_method`` / ``pairblock_tile`` are read inside the trial
    through the class's normal resolution path."""
    from .. import _global_options
    from ..parallel.runtime import CurrentMesh
    from ..pmesh import ParticleMesh
    import numpy as np

    box = float(ctx.get('box', 1000.0))
    nbins = int(ctx.get('nbins', 3))
    nmesh = int(ctx.get('nmesh', 64))
    rng = np.random.RandomState(int(ctx.get('seed', 7)))
    npart = int(ctx['npart'])
    pos = rng.uniform(0.0, box, size=(npart, 3))
    w = np.ones(npart)
    mesh = CurrentMesh.resolve(None)

    def once():
        from ..algorithms.bispectrum import (direct_bispectrum,
                                             fft_bispectrum)
        method = _global_options['bspec_method']
        if method == 'direct':
            tile = _global_options['pairblock_tile']
            B, _ = direct_bispectrum(
                pos, w, box, nbins,
                tile=None if tile in (None, 'auto') else int(tile),
                comm=mesh)
        else:
            import jax.numpy as jnp
            pm = ParticleMesh(Nmesh=nmesh, BoxSize=box,
                              dtype=ctx.get('dtype', 'f4'),
                              comm=mesh)
            delta = pm.paint(jnp.asarray(pos, pm.dtype), 1.0)
            B, _ = fft_bispectrum(pm, pm.r2c(delta), nbins)
        return float(np.nansum(B))
    return once


def bspec_space():
    return SearchSpace('bspec', ('bspec_method', 'pairblock_tile'),
                       _bspec_candidates, _bspec_runner)


def default_spaces():
    """``{op: SearchSpace}`` of every built-in space."""
    return {'paint': paint_space(), 'fft': fft_space(),
            'exchange': exchange_space(), 'ingest': ingest_space(),
            'bspec': bspec_space()}
