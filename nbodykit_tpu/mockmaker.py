"""Mock field/catalog generation.

Reference: ``nbodykit/mockmaker.py`` — Gaussian realizations (:7,:143),
lognormal transform (:213), Poisson sampling with Zel'dovich
displacement readout (:246). TPU redesign:

- the data phase of a lognormal mock is two cached programs, so that a
  campaign of mocks (a new seed each) traces and compiles nothing after
  its first: :func:`_fields_program` (white noise, delta_k and the
  three psi_k, four c2r, the lognormal rate and its Poisson counts,
  under ``lognormal.fields``; sqrt(P(k)/V) is :func:`_amplitude`'s,
  made once a geometry from the power's f8 path on the host) and
  :func:`_sample_program` (cells to rows, jitter, displacement, wrap,
  under ``lognormal.sample``).  On the CPU
  the drawn rows are the op-by-op recipe's to the bit: no product
  there meets an addition a fused multiply-add could take;
- the Poisson sample is written into a capacity taken from a ladder,
  :func:`sample_rung`, whose first rung is a function of the expected
  count nbar V alone and holds Poisson(nbar V) to
  :data:`RUNG_SIGMAS` sigma: every seed of one configuration shares
  its shapes, and those of everything downstream (the paint's sort,
  FFTPower's sums).  The drawn count is fetched once
  (``sync.lognormal.count``) to pick the rung; a count over it climbs
  (``lognormal.overflow``), nothing is dropped.  Rows past the count
  carry ``Selection`` False and zeros;
- cells become rows without a per-cell scatter (:func:`rows_to_cells`:
  a scatter over segments of 64 cells, a cumsum, one gather of the
  segment's row a particle), in raster order, so the selected rows are
  the exact-count sample's, device-count invariant without the
  reference's mpsort pass (mockmaker.py:344).
"""

from functools import lru_cache as _lru_cache

import numpy as np
import jax
import jax.numpy as jnp

from .base.mesh import Field
from .diagnostics import counter, fetch, gauge, instrumented_jit, scope
from .parallel.exchange import RUNG, ladder_capacity

#: the first rung of the sample's capacity holds Poisson(nbar V) to
#: this many sigma (the count is a Poisson draw of the whole box's
#: rate: the lognormal rate is normalised to mean nbar exactly): one
#: seed in 1e9 climbs.  At nbar V = 1e7 the rung is 0.19% over the mean
RUNG_SIGMAS = 6.0


def power_amplitude(pm, linear_power):
    """sqrt(P(k) / V) on the half-complex mesh, 0 at k = 0.

    P is evaluated on the host in f8, once a distinct |k| where the
    fundamental is one on every axis (as upstream evaluates Plin on its
    k grid): ``LinearPower``'s device table is off its own f8 path by
    up to 1.3e-4, which moved the Poisson counts of 1.8e-6 of the cells
    at 512^3 against the f8 reference (PERF.md, PR 39)."""
    N = [int(n) for n in pm.Nmesh]
    kf = 2 * np.pi / np.asarray(pm.BoxSize, 'f8')
    i = [np.fft.fftfreq(n, 1.0 / n) for n in N[:2]] \
        + [np.arange(N[2] // 2 + 1, dtype='f8')]
    # the transposed complex layout: (ky, kx, kz)
    shape = [(1, N[0], 1), (N[1], 1, 1), (1, 1, N[2] // 2 + 1)]
    V = float(np.prod(pm.BoxSize))
    dtype = np.float32 if pm.dtype.itemsize <= 4 else np.float64

    def amp(k):
        k = np.asarray(k, 'f8')
        with np.errstate(divide='ignore', invalid='ignore'):
            pk = np.where(k > 0, np.asarray(linear_power(k), 'f8'), 0.0)
        return np.sqrt(np.maximum(pk, 0.0) / V)

    if np.all(kf == kf[0]):
        isq = sum((a.astype(np.int32) ** 2).reshape(s)
                  for a, s in zip(i, shape))
        table = amp(kf[0] * np.sqrt(np.arange(int(isq.max()) + 1.0)))
        return jnp.asarray(table.astype(dtype)[isq])
    k2 = sum(((kf[j] * i[j]) ** 2).reshape(shape[j]) for j in range(3))
    return jnp.asarray(amp(np.sqrt(k2)).astype(dtype))


def gaussian_complex_fields(pm, linear_power, seed,
                            unitary_amplitude=False, inverted_phase=False,
                            compute_displacement=False):
    """delta_k (and optionally psi_k) for a linear power spectrum.

    delta_k = eta * sqrt(P(k)/V); psi_i(k) = (i k_i / k^2) delta_k.
    Reference recipe: mockmaker.py:7-141.

    Returns (delta_k Field, [psi_x, psi_y, psi_z] Fields or None).
    """
    return _complex_fields(pm, seed, power_amplitude(pm, linear_power),
                           unitary_amplitude, inverted_phase,
                           compute_displacement)


def _complex_fields(pm, seed, amplitude, unitary, inverted, displacement):
    """:func:`gaussian_complex_fields` of :func:`power_amplitude`'s
    ``amplitude``, the part a program traces; ``seed`` is an int or a
    typed PRNG key."""
    eta = pm.generate_whitenoise(seed, unitary=unitary,
                                 inverted_phase=inverted)
    with jax.ensure_compile_time_eval():
        # the squares as eager ops make them, also inside a program: a
        # fused multiply-add would round k2 once where they round twice
        kx, ky, kz = pm.k_list(dtype=jnp.float64 if pm.dtype.itemsize > 4
                               else jnp.float32)
        sq = [k ** 2 for k in (kx, ky, kz)]
    k2 = sq[0] + sq[1] + sq[2]
    amp = amplitude.astype(eta.real.dtype)
    delta_k = jnp.where(k2 == 0, 0.0, eta * amp)

    disp_k = None
    if displacement:
        k2safe = jnp.where(k2 == 0, 1.0, k2)
        disp_k = [
            Field(jnp.where(k2 == 0, 0.0,
                            1j * kdir / k2safe * delta_k), pm, 'complex')
            for kdir in (kx, ky, kz)]
    return Field(delta_k, pm, 'complex'), disp_k


def gaussian_real_fields(pm, linear_power, seed,
                         unitary_amplitude=False, inverted_phase=False,
                         compute_displacement=False):
    """Real-space delta (and displacement vector fields); reference
    mockmaker.py:143-210."""
    return _real_fields(pm, seed, power_amplitude(pm, linear_power),
                        unitary_amplitude, inverted_phase,
                        compute_displacement)


def _real_fields(pm, seed, amplitude, unitary, inverted, displacement):
    """:func:`gaussian_real_fields` of an ``amplitude`` (as
    :func:`_complex_fields`)."""
    delta_k, disp_k = _complex_fields(pm, seed, amplitude, unitary,
                                      inverted, displacement)
    delta = delta_k.c2r()
    disp = None
    if disp_k is not None:
        disp = [d.c2r() for d in disp_k]
    return delta, disp


def lognormal_transform(density, bias=1.0):
    """delta -> exp(b*delta), normalized to unit mean (reference
    mockmaker.py:213-243)."""
    value = jnp.exp(bias * density.value)
    value = value / value.mean()
    return Field(value, density.pm, 'real')


#: below this rate a count is Knuth's, above it ``jax.random.poisson``'s
#: transformed rejection (where jax switches too)
KNUTH_MAX = 10.0


def _exp_neg(lam):
    """exp(-lam) in f4 for 0 <= lam < :data:`KNUTH_MAX`, by
    multiplications and additions alone: Cody-Waite reduction by ln 2,
    cephes' ``expf`` polynomial and an exact power of two, within an ulp
    or two on every backend (a backend's own exp need not be)."""
    n = jnp.floor(lam * 1.44269504088896341 + 0.5)     # 0 .. 14
    r = n * 0.693359375 - lam + n * -2.12194440e-4
    p = 1.9875691500e-4
    for c in (1.3981999507e-3, 8.3334519073e-3, 4.1665795894e-2,
              1.6666665459e-1, 5.0000001201e-1):
        p = p * r + c
    y = p * (r * r) + r + 1.0
    for b in (8.0, 4.0, 2.0, 1.0):      # times 2^-n, by n's bits
        hit = n >= b
        y, n = jnp.where(hit, y * 2.0 ** -b, y), jnp.where(hit, n - b, n)
    return y


def poisson_counts(key, lam):
    """Poisson counts (int32) of the rates ``lam`` from ``key``:
    ``jax.random.poisson(key, lam)``'s draw without a transcendental on
    its uniforms.  Below :data:`KNUTH_MAX`, Knuth's count of the
    uniforms whose running product stays above exp(-lam), with jax's
    key schedule and uniforms, the product in place of its sum of logs;
    above it, jax's rejection.  A v5e's log is off the CPU's by enough
    to move 6.4e-6 of the cells' counts against the same uniforms
    (PERF.md, PR 39); a product and :func:`_exp_neg` are the same to an
    ulp on every backend, so a seed's mock is one on the chip and on
    the host."""
    lam = jnp.asarray(lam, jnp.float32)
    knuth = lam < KNUTH_MAX
    bound = _exp_neg(jnp.where(knuth, lam, 0.0))

    def body(carry):
        rng, k, prod = carry
        rng, sub = jax.random.split(rng)
        prod = prod * jax.random.uniform(sub, lam.shape, jnp.float32)
        return rng, k + (prod > bound).astype(jnp.int32), prod

    _, k, _ = jax.lax.while_loop(
        lambda carry: (carry[2] > bound).any(), body,
        (key, jnp.zeros(lam.shape, jnp.int32),
         jnp.ones(lam.shape, jnp.float32)))
    return jax.lax.cond(knuth.all(), lambda: k, lambda: jnp.where(
        knuth, k, jax.random.poisson(key, lam, dtype=jnp.int32)))


def sample_rung(expected, drawn):
    """The sample's capacity for ``drawn`` particles of an expected
    ``expected``: the first rung holds Poisson(expected) to
    :data:`RUNG_SIGMAS` sigma, rounded up to 1024 rows; a count over
    it climbs :func:`~nbodykit_tpu.parallel.exchange.ladder_capacity`'s
    17/16 ladder from there.  Returns ``(rung, climbs)``."""
    e = max(float(expected), 0.0)
    base = max(int(np.ceil((e + RUNG_SIGMAS * np.sqrt(e)) / 1024.0)), 1) \
        * 1024
    rung = ladder_capacity(int(drawn), base, 1, slack=1.0)
    num, den = RUNG
    climbs = 0
    while -(-base * num ** climbs // den ** climbs) < rung:
        climbs += 1
    return rung, climbs


def _select8(lanes, t):
    """``lanes[:, t]`` of an (n, m) int array, by compares and an
    integer sum of one non-zero term: the value's own bits (a float
    one-hot sum turns -0.0 into +0.0), and no gather.  Named for the
    8-lane blocks it first picked from; the cell's oracle test patches
    it to read a segment's first cell."""
    hit = jnp.arange(lanes.shape[1], dtype=t.dtype) == t[:, None]
    return jnp.where(hit, lanes, 0).sum(axis=1, dtype=lanes.dtype)


#: cells of the mesh's last axis in one segment of :func:`rows_to_cells`:
#: a segment's row of its table is 4 x 64 words, two 128-lane tiles
SEGMENT = 64

#: output rows a step of :func:`rows_to_cells`'s loop places: its one
#: row read is (rows, 4 SEGMENT) words, 0.5 GB; all 1e7 rows at once
#: held 13.4 GB of temporaries at 512^3
ROWS_PER_STEP = 1 << 19


def _steps(nrows):
    """``(rows, steps)``: :func:`rows_to_cells`'s loop over ``nrows``."""
    step = min(ROWS_PER_STEP, nrows)
    return step, -(-nrows // step)


def rows_to_cells(counts, psi, nrows):
    """The cell of each of ``nrows`` output rows when cell c holds
    ``counts[c]`` of them in raster order (``jnp.repeat`` of the flat
    cell index), and the three ``psi`` fields there.  Rows past the
    total get some cell; the caller masks them.

    ``jnp.repeat`` with array repeats scatters one update per cell
    (N^3 of them, mostly colliding empty cells: 1.75 s at 512^3 on a
    v5e, PERF.md), and a TPU gather costs 10-20 ns an index whatever
    the slice's width, so a binary search of 27 gathers is slower
    still.  Here the mesh's last axis is cut into segments of
    :data:`SEGMENT` cells, and a row of output n finds its cell with
    one gather:

    1. its segment: a scatter of ones at the segments' first output
       rows and a cumsum over the output (empty segments collide
       harmlessly: the last segment starting at or before n holds n);
    2. its cell in the segment, and psi there: one gather of the
       segment's row of a table holding, side by side, the cells'
       cumulative counts over the whole mesh (so n is compared as it
       is) and the three psi values (as their bits: an int is never
       flushed as a denormal); the cell is the number of counts at or
       below n, and psi its lane's bits, picked by compares and an
       integer sum of one non-zero term (-0.0 kept).

    Step 2 runs :data:`ROWS_PER_STEP` rows at a time.  The gather takes
    whole rows, 4 x 64 words: a gather of part of a row lowers to a
    loop of one index an iteration (22 s a call at 512^3, PERF.md), and
    the TPU tiles the last two axes (8, 128), so a last axis of 8 or 1
    would be padded 16 or 128 times."""
    counter('lognormal.sample.trace.segment').add(1)    # once a program
    N0, N1, N2 = counts.shape
    G = SEGMENT
    R, S = N0 * N1, -(-N2 // G)
    pad = ((0, 0), (0, G * S - N2))
    n = jnp.pad(counts.reshape(R, N2).astype(jnp.int32), pad)
    cum = jnp.cumsum(n, axis=1, dtype=jnp.int32)
    rowtot = cum[:, -1]
    cum = cum + (jnp.cumsum(rowtot, dtype=jnp.int32) - rowtot)[:, None]
    first = (cum - n)[:, ::G].reshape(R * S)
    step, nsteps = _steps(nrows)
    seg = jnp.cumsum(jnp.zeros(nsteps * step, jnp.int32).at[first].add(
        1, mode='drop'), dtype=jnp.int32) - 1
    parts = [cum] + [jax.lax.bitcast_convert_type(
        jnp.pad(d.reshape(R, N2).astype(jnp.float32), pad), jnp.int32)
        for d in psi]
    # a part at a time: a (R S, G) part is padded to 128 lanes, and the
    # four at once held 2.1 GB more at 512^3
    table = jnp.zeros((R * S, 4 * G), jnp.int32)
    for k, p in enumerate(parts):
        table = table.at[:, k * G:(k + 1) * G].set(p.reshape(R * S, G))

    def place(args):
        seg, row = args
        block = table[seg]                              # (step, 4 G)
        t = jnp.minimum((block[:, :G] <= row[:, None]).sum(
            axis=1, dtype=jnp.int32), G - 1)
        disp = jnp.stack([_select8(block[:, G * k:G * (k + 1)], t)
                          for k in (1, 2, 3)], axis=-1)
        return (seg // S) * N2 + (seg % S) * G + t, disp

    rows = jnp.arange(nsteps * step, dtype=jnp.int32)
    cell, disp = jax.lax.map(place, (seg.reshape(nsteps, step),
                                     rows.reshape(nsteps, step)))
    return (cell.reshape(-1)[:nrows],
            jax.lax.bitcast_convert_type(disp.reshape(-1, 3)[:nrows],
                                         jnp.float32))


class _Power(object):
    """``linear_power`` as a key of :func:`_amplitude`: a
    ``LinearPower`` by its interpolation table's bytes, a function of
    everything its f8 path reads (its ``sigma8`` or redshift may change
    under one object, and two objects of one table share an entry); any
    other callable by itself."""

    def __init__(self, fn):
        self.fn = fn
        table = getattr(fn, 'to_table', None)
        self.key = (fn,) if table is None else tuple(
            np.asarray(t).tobytes() for t in table())

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, _Power) and self.key == other.key


def _mesh(nmesh, boxsize, dtype, comm):
    from .pmesh import ParticleMesh
    return ParticleMesh(nmesh, boxsize, dtype=dtype, comm=comm)


@_lru_cache(maxsize=2)
def _amplitude(nmesh, boxsize, dtype, comm, x64, power):
    """:func:`power_amplitude` of one geometry and power (a
    :class:`_Power`), made on a miss and kept on the device (0.27 GB at
    512^3): the fields program's argument, so that the power is
    evaluated once a geometry, not once a seed."""
    return power_amplitude(_mesh(nmesh, boxsize, dtype, comm),
                           power.fn)


@_lru_cache(maxsize=8)
def _fields_program(nmesh, boxsize, dtype, comm, x64, bias, nbar, unitary,
                    inverted):
    """``(raw, jitted)``: ``prog(key, amplitude) -> (counts, total,
    psi)``, the Poisson counts of the lognormal rate, their sum and the
    three displacement fields of one realisation (``amplitude`` is
    :func:`_amplitude`'s).  Cached on everything the body reads: the
    mesh's geometry, dtype and device mesh, ``jax_enable_x64``, bias,
    nbar and the two amplitude switches."""
    pm = _mesh(nmesh, boxsize, dtype, comm)
    rate = nbar * float(np.prod(pm.cellsize))

    def raw(key, amplitude):
        with scope('lognormal.fields'):
            delta, disp = _real_fields(pm, key, amplitude, unitary,
                                       inverted, True)
            # Lagrangian bias: the Zel'dovich displacement supplies the
            # (Eulerian) +1 (reference mockmaker.py:289)
            overdensity = lognormal_transform(delta, bias=bias - 1.0)
            lam = rate * overdensity.value
            k_pois, _ = jax.random.split(key)
            counts = poisson_counts(k_pois, lam)  # invariant
            return counts, counts.sum(), [d.value for d in disp]
    return raw, instrumented_jit(raw, label='lognormal.fields')


@_lru_cache(maxsize=8)
def _sample_program(nmesh, boxsize, comm, rung):
    """``(raw, jitted)``: ``prog(key, counts, total, psi, vfac, f) ->
    (pos, vel, voff, selection)`` with ``rung`` rows, the first
    ``total`` of them the sample in raster order: cell corner, uniform
    in-cell jitter, Zel'dovich displacement read at the cell (the nnb
    readout), periodic wrap, v = vfac psi, voff = f psi.  Cached on the
    geometry, the device mesh and the rung."""
    N0, N1, N2 = nmesh
    H = np.asarray(boxsize, 'f8') / np.asarray(nmesh, 'f8')

    def raw(key, counts, total, psi, vfac, f):
        with scope('lognormal.sample'):
            _, k_shift = jax.random.split(key)
            cell, disp = rows_to_cells(counts, psi, rung)
            valid = jnp.arange(rung) < total
            keep = valid[:, None]
            cell = jnp.where(valid, cell, 0)
            i0 = cell // (N1 * N2)
            i1 = (cell // N2) % N1
            i2 = cell % N2
            H32 = jnp.asarray(H, jnp.float32)
            corner = jnp.stack([i0, i1, i2], axis=-1).astype(jnp.float32) \
                * H32
            # uniform in-cell jitter, keyed independently of the layout;
            # the draw is prefix-stable: row r's is the exact-count's.
            # The select keeps its product a rounding of its own, as the
            # eager ops do (a fused multiply-add rounds once; the
            # corner's product i H is exact at H = 625/64)
            jitter = jnp.where(keep, jax.random.uniform(
                k_shift, (rung, 3), jnp.float32) * H32, 0)
            pos = corner + jitter
            box = jnp.asarray(boxsize, pos.dtype)
            pos = jnp.mod(pos + disp, box)
            vel = (disp * vfac).astype(pos.dtype)
            voff = (disp * f).astype(pos.dtype)
            return (jnp.where(keep, pos, 0), jnp.where(keep, vel, 0),
                    jnp.where(keep, voff, 0), valid)
    return raw, instrumented_jit(raw, label='lognormal.sample')


def lognormal_sample(pm, linear_power, seed, nbar, bias=1.0, vfac=0.0,
                     f=0.0, unitary_amplitude=False, inverted_phase=False):
    """A Poisson-sampled lognormal realisation with Zel'dovich
    displacements (reference mockmaker.py:7-357), as two cached
    programs.

    Returns ``dict(Position, Velocity, VelocityOffset, Selection, N,
    rung)``: device arrays of ``rung`` rows (:func:`sample_rung`), the
    first ``N`` (a host int, the drawn count) the sample in raster
    order, the rest zeros with ``Selection`` False."""
    key = jax.random.key(seed)
    geometry = (tuple(int(n) for n in pm.Nmesh),
                tuple(float(b) for b in pm.BoxSize))
    mesh = geometry + (str(np.dtype(pm.dtype)), pm.comm,
                       bool(jax.config.jax_enable_x64))
    amplitude = _amplitude(*mesh, _Power(linear_power))
    raw, jitted = _fields_program(
        *mesh, float(bias), float(nbar), bool(unitary_amplitude),
        bool(inverted_phase))
    with scope('lognormal.fields'):
        counts, total, psi = jitted(key, amplitude)
    with scope('lognormal.sample') as sc:
        # the rung is a static shape of what follows: one host sync
        N = int(fetch(total, 'lognormal.count'))
        rung, climbs = sample_rung(nbar * float(np.prod(pm.BoxSize)), N)
        sc.set(segment=SEGMENT, steps=_steps(rung)[1])
        counter('lognormal.overflow').add(climbs)
        gauge('lognormal.capacity').set(rung)
        gauge('lognormal.fill').set(N / float(rung))
        raw, jitted = _sample_program(*geometry, pm.comm, rung)
        pos, vel, voff, sel = jitted(key, counts, total, psi,
                                     np.float32(vfac), np.float32(f))
    return dict(Position=pos, Velocity=vel, VelocityOffset=voff,
                Selection=sel, N=N, rung=rung)
