"""Stable counting/radix ordering for small-alphabet keys.

Several hot paths order particles by a *small* integer key — the paint
bucketing (ops/paint.py: tile id), the exchange routing
(parallel/exchange.py: destination device), the cell hash
(ops/devicehash.py: grid cell). They all reached for ``jnp.argsort``,
which XLA lowers to a bitonic network on TPU: O(n log^2 n) passes over
HBM — the measured dominant cost of the mxu paint at 256^3 (see
docs/PERF.md).

For keys drawn from a known alphabet of D values a *stable counting
sort* does the same job in O(n) with TPU-shaped ops only:

  rank[i]  = #{j < i : key[j] == key[i]}   (chunked scan: one-hot
             cumsum per chunk + per-digit running totals carried
             across chunks; the one-hot trick ``(cumO * O).sum(1)``
             reads the cumsum at each row's own digit with NO gather)
  start[d] = exclusive cumsum of the digit histogram (final carry)
  dest[i]  = start[key[i]] + rank[i]       (a permutation)

and one unique-index scatter materializes the order (or routes the
payload directly). For alphabets too wide for one pass (the paint's
tile id reaches ~16k at Nmesh=1024; hash-grid cell ids reach 1e6+),
k stable LSD passes over balanced base-ceil(D^(1/k)) digits compose.

The reference meets the same need with mpsort's distributed C
histogram sort (consumed at nbodykit/base/catalog.py:1285,
nbodykit/mockmaker.py:344); this module is the single-device,
in-graph building block of that design.
"""

import numpy as np
import jax
import jax.numpy as jnp


def pad_digits(digit, D, chunk):
    """Pad a digit stream to a chunk multiple with sentinel digit D-1
    (shapes stay static). The CONTRACT shared by the XLA and Pallas
    rank passes: padded ranks are sliced off by the caller and the
    sentinel's histogram count must be corrected by ``hist[D-1] -=
    npad``. Returns (padded (nch, chunk) i32, npad)."""
    n = digit.shape[0]
    nch = max(1, -(-n // chunk))
    npad = nch * chunk - n
    dig_p = jnp.concatenate(
        [digit.astype(jnp.int32),
         jnp.full((npad,), D - 1, jnp.int32)]).reshape(nch, chunk)
    return dig_p, npad


def _pass_rank_hist(digit, D, chunk):
    """rank[i] = # of j < i with digit[j] == digit[i]; hist = digit
    histogram. One scan over chunks; exact in i32 (per-chunk one-hot
    cumsum stays < chunk <= 2^24 in f32, cross-chunk totals are i32).

    digit : (n,) int32 in [0, D) — caller pads/clamps out-of-range.
    Returns (rank (n,) i32, hist (D,) i32).
    """
    n = digit.shape[0]
    dig_p, npad = pad_digits(digit, D, chunk)
    Mp = dig_p.size

    def step(base, d_c):
        O = jax.nn.one_hot(d_c, D, dtype=jnp.float32)      # (C, D)
        cumO = jnp.cumsum(O, axis=0)
        # one-hot picks cumO[i, d_i]: inclusive count -> exclusive
        rank_in = (cumO * O).sum(axis=1).astype(jnp.int32) - 1
        rank_c = jnp.take(base, d_c, axis=0) + rank_in
        base = base + cumO[-1].astype(jnp.int32)
        return base, rank_c

    # data-derived zero init: under shard_map the scan carry must have
    # the same varying-manual-axes type as the per-step update (same
    # convention as ops/paint.py's scan carries)
    base0 = jnp.zeros((D,), jnp.int32) + dig_p.ravel()[0] * 0
    hist, ranks = jax.lax.scan(step, base0, dig_p)
    ranks = ranks.reshape(Mp)[:n]
    hist = hist.at[D - 1].add(-npad)
    return ranks, hist


# rank-pass engine: 'xla' (the scan above), 'pallas' (VMEM kernel,
# ops/radix_pallas.py — ~D columns less HBM traffic per element), or
# 'auto'. Module-level default so hardware A/B (bench.py --prim) can
# flip it. 'auto' resolves to 'xla' everywhere: the Pallas kernel
# compiles for a v5e at the paint's shapes (tests/test_tpu_compile.py)
# but has not been timed on the chip.  Flip to pallas-on-TPU only
# when a chip measurement says it wins.
DEFAULT_ENGINE = 'auto'


def _rank_hist(digit, D, chunk, engine=None):
    engine = engine or DEFAULT_ENGINE
    if engine == 'auto':
        engine = 'xla'
    if engine == 'pallas':
        from .radix_pallas import pass_rank_hist_pallas
        return pass_rank_hist_pallas(digit, D)
    return _pass_rank_hist(digit, D, chunk)


def stable_digit_dest(digit, D, chunk=4096, engine=None):
    """dest[i] = stable-counting-sort position of element i; a
    permutation of [0, n)."""
    rank, hist = _rank_hist(digit, D, chunk, engine)
    start = jnp.cumsum(hist) - hist           # exclusive
    return jnp.take(start, digit.astype(jnp.int32), axis=0) + rank


def stable_order(key, D):
    """Backend-dispatched stable ordering: the counting sort on MXU
    hardware, native argsort elsewhere — the ONE policy point for the
    argsort-replacement call sites (devicehash, dist_sort; paint
    routes through its order_method option instead)."""
    from ..utils import is_mxu_backend
    if is_mxu_backend():
        return stable_key_order(key, D)
    return jnp.argsort(key)


def order_keys(key, D, method='auto'):
    """Stable ordering with an EXPLICIT engine choice — the dispatch
    behind the ``paint_order`` option (ops/paint.py bucketing
    and the one-sort deposit kernels).

    method : 'argsort' (one bitonic lax sort — O(n log^2 n) HBM passes
        on TPU, the fast native sort on CPU), 'radix'
        (:func:`stable_key_order` — O(n) counting passes over the
        [0, D) alphabet, the TPU-shaped choice), or 'auto' (radix on
        MXU backends, argsort elsewhere). Both engines are stable, so
        the resulting permutation is identical and the choice is pure
        performance (tests/test_radix.py asserts the equality).
    """
    if method == 'auto':
        from ..utils import is_mxu_backend
        method = 'radix' if is_mxu_backend() else 'argsort'
    if method == 'radix':
        return stable_key_order(key, D)
    if method == 'argsort':
        return jnp.argsort(key)
    # a typo must not silently measure/record the wrong engine
    raise ValueError("unknown order method %r (choose "
                     "'auto'/'radix'/'argsort')" % (method,))


def _invert_perm(dest):
    """order[dest[i]] = i (scatter with provably unique indices)."""
    n = dest.shape[0]
    iot = jnp.arange(n, dtype=jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[dest].set(
        iot, unique_indices=True)


def stable_key_order(key, D, chunk=4096, radix=None, engine=None):
    """Permutation ``order`` with ``key[order]`` stably sorted.

    Drop-in for ``jnp.argsort(key)`` when keys are known to lie in
    [0, D) (out-of-range keys must be clamped to D-1 by the caller —
    the bucketing call sites already route invalid slots to a trash
    value). One counting pass when D <= ``radix`` threshold, else
    k = ceil(log_radix(D)) LSD passes over balanced base-ceil(D^(1/k))
    digits.

    chunk : scan chunk size; per-chunk one-hot is (chunk, R) f32.
    """
    n = key.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    key = key.astype(jnp.int32)
    if radix is None:
        radix = 1024
    if D <= radix:
        return _invert_perm(stable_digit_dest(key, D, chunk, engine))
    # k LSD passes over balanced base-R digits, R = ceil(D^(1/k)):
    # stable passes low-digit-first compose into the full order
    npasses = int(np.ceil(np.log(D) / np.log(radix)))
    R = int(np.ceil(D ** (1.0 / npasses)))
    order = None
    f = 1
    for _ in range(npasses):
        k_cur = key if order is None else jnp.take(key, order, axis=0)
        dig = (k_cur // f) % R
        step = _invert_perm(stable_digit_dest(dig, R, chunk, engine))
        order = step if order is None else jnp.take(order, step, axis=0)
        f *= R
    return order
