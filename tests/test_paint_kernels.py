"""Equivalence suite for every paint engine.

``set_options(paint_method=...)`` can select ANY engine of
ops/paint.py, so each one must deposit exactly the same mesh as the
reference scatter kernel — across resamplers, wrap seams, halo/origin
offsets and the 8-device mesh. The list is
``conftest.PAINT_CANDIDATES``: every engine with the options that
select it.

Also the dropped-deposit observability contract (ISSUE 8): the eager
mxu bucket-overflow backoff must bump ``paint.dropped`` before it
heals, and the healed mesh must conserve mass.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import nbodykit_tpu
from nbodykit_tpu import _global_options
from nbodykit_tpu.diagnostics import REGISTRY
from nbodykit_tpu.ops.paint import (paint_local, paint_local_sorted,
                                    paint_local_segsum,
                                    paint_local_streams,
                                    paint_local_mxu)
from conftest import PAINT_CANDIDATES as CANDS

# (n0l, N1, N2, p0, origin) — same geometry convention as
# tests/test_paint_mxu.py: interior block, origin-offset block, and a
# block whose halo-extended rows wrap the periodic boundary
GEOMETRIES = [
    (16, 16, 16, 16, 0),
    (12, 16, 16, 32, 5),
    (10, 24, 16, 64, 59),
]


@pytest.fixture(autouse=True)
def _clean_state():
    saved = _global_options.copy()
    REGISTRY.reset()
    yield
    REGISTRY.reset()
    _global_options.clear()
    _global_options.update(saved)


def _counter(name):
    snap = REGISTRY.snapshot().get(name)
    return snap['value'] if snap else 0


def _edge_positions(rng, n, n0l, p0, N1, N2, origin):
    """Positions slamming every hazard at once: x rows pinned to the
    block edges, the origin offset and the periodic seam (the
    n0l-boundary cases of ISSUE 8), y/z pinned to their wrap seams,
    plus a uniform fill."""
    pos = rng.uniform(0.0, p0, (n, 3))
    pos[:, 1] = rng.uniform(0.0, N1, n)
    pos[:, 2] = rng.uniform(0.0, N2, n)
    xedges = np.array([0.0, 0.3, p0 - 0.25, origin % p0,
                       (origin + 0.25) % p0,
                       (origin + n0l - 0.25) % p0,
                       (origin + n0l + 0.25) % p0])
    yedges = np.array([0.0, 0.25, N1 - 0.25])
    zedges = np.array([0.0, 0.25, N2 - 0.25])
    ne = min(n // 2, 7 * 8)
    pos[:ne, 0] = np.tile(xedges, -(-ne // len(xedges)))[:ne]
    pos[:ne, 1] = np.tile(yedges, -(-ne // len(yedges)))[:ne]
    pos[:ne, 2] = np.tile(zedges, -(-ne // len(zedges)))[:ne]
    return jnp.asarray(pos)


def _run_candidate(opts, pos, mass, shape, res, period, origin):
    """Invoke the LOCAL kernel a candidate's options select — with a
    non-default chunk where the candidate exercises a chunked loop, so
    the padded fori_loop paths are covered too."""
    method = opts['paint_method']
    args = (pos, mass, shape)
    kw = dict(resampler=res, period=period, origin=origin)
    if method == 'scatter':
        chunk = 97 if opts.get('paint_chunk_size') == 1024 * 1024 * 4 \
            else None
        return paint_local(*args, chunk=chunk, **kw)
    if method == 'sort':
        return paint_local_sorted(*args, **kw)
    if method == 'segsum':
        return paint_local_segsum(
            *args, order_method=opts.get('paint_order', 'argsort'),
            **kw)
    if method == 'streams':
        return paint_local_streams(
            *args, streams=opts['paint_streams'], chunk=101, **kw)
    if method == 'mxu':
        return paint_local_mxu(*args, deposit='xla', **kw)
    raise AssertionError('unknown candidate method %r' % method)


@pytest.mark.parametrize('res', ['cic', 'tsc'])
@pytest.mark.parametrize('name', sorted(CANDS))
def test_local_kernel_equivalence(name, res):
    rng = np.random.default_rng(42)
    for (n0l, N1, N2, p0, origin) in GEOMETRIES:
        shape, period = (n0l, N1, N2), (p0, N1, N2)
        pos = _edge_positions(rng, 400, n0l, p0, N1, N2, origin)
        mass = jnp.asarray(rng.uniform(0.5, 2.0, 400))
        ref = paint_local(pos, mass, shape, resampler=res,
                          period=period, origin=origin)
        got = _run_candidate(CANDS[name], pos, mass, shape, res,
                             period, origin)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-10, atol=1e-12,
                                   err_msg='%s %s %r' % (name, res,
                                                         shape))


@pytest.mark.parametrize('name', sorted(CANDS))
def test_multi_device_equivalence(name, cpu8):
    """Every candidate, end to end through ``pm.paint`` on the
    8-device mesh: allclose to the scatter oracle, exact mass
    conservation, and bit-identical across repeated paints (the
    determinism claim an A/B of two engines relies on)."""
    from nbodykit_tpu.pmesh import ParticleMesh
    rng = np.random.default_rng(7)
    n = 500
    pos = rng.uniform(0.0, 64.0, (n, 3))
    # pin a band to the inter-device slab boundaries (n0_cell = 4
    # cells per device at Nmesh=32 / box 64) and the periodic seam
    slab = 64.0 / 8
    edges = np.array([0.0, 0.01, slab, slab - 0.01, 3 * slab,
                      63.99, 5 * slab + 0.01, 7 * slab])
    pos[:len(edges) * 4, 0] = np.tile(edges, 4)
    spos = jnp.asarray(pos)
    pm = ParticleMesh(Nmesh=32, BoxSize=64.0, dtype='f8', comm=cpu8)

    # one jitted program per candidate: options are read at trace
    # time, and the persistent compile cache keeps re-runs cheap.
    # return_dropped satisfies the traced-mxu overflow contract; the
    # count must come back zero for every candidate here.
    def painted(options):
        with nbodykit_tpu.set_options(**options):
            fn = jax.jit(lambda p: pm.paint(p, 1.0,
                                            return_dropped=True))
            mesh, dropped = fn(spos)
            again, _ = fn(spos)
        assert int(dropped) == 0
        # bit-identical: same program, same inputs, same mesh
        np.testing.assert_array_equal(np.asarray(mesh),
                                      np.asarray(again))
        return mesh
    ref = painted({'paint_method': 'scatter'})
    got = painted(CANDS[name])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-12, atol=1e-12)
    assert np.isclose(float(jnp.sum(got)), n, rtol=1e-10)


def test_streams_candidates_capped_by_memory_plan():
    """``memory_plan`` prices the streams kernel's replica meshes: every
    stream count fits at a small shape, none next to a 1024^3 field
    on one 16 GB chip (ISSUE 8 acceptance: the 1024^3 pipeline must
    stay inside budget)."""
    from nbodykit_tpu.pmesh import memory_plan
    for k in (2, 4, 8):
        assert memory_plan(64, 10_000, paint_method='streams',
                           paint_streams=k, hbm_bytes=16e9)['fits']
        assert not memory_plan(1024, 1e8, paint_method='streams',
                               paint_streams=k,
                               hbm_bytes=16e9)['fits']


def test_mxu_one_cell_needs_no_retry():
    """Every particle in one cell, eagerly through ``pm.paint``: one
    tile bucket holds all n and the deposit takes more pieces.  There
    is no capacity to overflow: one program, one call, nothing lands
    in ``paint.dropped``."""
    from nbodykit_tpu.pmesh import ParticleMesh
    rng = np.random.default_rng(3)
    n = 3000
    pos = jnp.asarray(rng.uniform(4.0, 4.9, (n, 3)))
    pm = ParticleMesh(Nmesh=16, BoxSize=16.0, dtype='f8')
    before = _counter('paint.trace.tile')
    with nbodykit_tpu.set_options(paint_method='mxu'):
        out = pm.paint(pos, 1.0)
    assert _counter('paint.trace.tile') - before == 1
    assert _counter('paint.dropped') == 0
    assert np.isclose(float(jnp.sum(out)), n, rtol=1e-10)
