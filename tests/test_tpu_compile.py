"""Compile the main path for a TPU v5e at the chip_smoke sizes, without
the chip (on-chip-measurement guide, section 2, rehearsal 3).

The TPU compiler is installed here and compiles for a device that is
described, not attached: it refuses what the chip's compiler would
refuse — a kernel's block shapes, a program that does not fit HBM.
Nothing runs, so these cases say nothing about results or times.

This is the ONLY file that describes a topology, and it does so inside
the ``topo`` fixture: only one process may load libtpu, and every
xdist worker imports every test file.

One compile of an exchange is ~20 s and of a paint ~2 min here (the
sort of 1e7 rows with its payload is most of it: 58.9 s alone on the
chip's host, PR 33), whatever the particle count.  Three more cases
are marked ``slow``: run the whole file (no ``-m 'not slow'``) before
a call that selects the Pallas deposit or takes four chips.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

NMESH, NPART = 512, 10 ** 7
#: what one v5e offers a program ("Used 16.50G of 15.75G hbm")
V5E_HBM = 15.75 * 2 ** 30
#: the lognormal sample program's temporaries at 512^3 when its rows
#: were placed by two gathers a row and chains of selects
SAMPLE_TEMP_BYTES = 4363914752


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def four_chips(topo):
    from nbodykit_tpu.parallel.runtime import AXIS
    return Mesh(np.array(topo.devices), (AXIS,))


@pytest.fixture(autouse=True)
def as_on_the_chip(monkeypatch):
    """Trace as the chip would: no x64 (the suite turns it on), the
    TPU branch of every ``is_mxu_backend()`` dispatch, and the compile
    cache off (an executable for a described device can be written to
    it but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    import nbodykit_tpu.utils
    monkeypatch.setattr(nbodykit_tpu.utils, 'is_mxu_backend',
                        lambda: True)
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update('jax_enable_compilation_cache', True)
        compilation_cache.reset_cache()


def _pm(nmesh, comm=None, box=1000.0):
    from nbodykit_tpu.parallel.runtime import use_mesh
    from nbodykit_tpu.pmesh import ParticleMesh
    with use_mesh(comm):
        return ParticleMesh(Nmesh=nmesh, BoxSize=box, dtype='f4',
                            comm=comm)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _paint_ops(compiled):
    """The compiled program's instructions under ``nbk.paint``, and
    what the tile paint promises of them: one sort of the particle
    count (the payload inside it), the deposit as a convolution, and
    no scatter or gather whose index count is the particles'."""
    lines = [line for line in compiled.as_text().splitlines()
             if 'nbk.paint' in line and ' = ' in line]
    ops = [(line.split(' = ', 1)[1], line) for line in lines]
    sorts = [head for head, _ in ops
             if re.search(r'\) sort\(', head.split('metadata')[0])]
    assert len(sorts) == 1, sorts
    # key, x, y, z, mass (and the iota that makes the sort stable)
    assert sorts[0].split(' sort(')[0].count('[%d]' % NPART) in (5, 6), \
        sorts[0]
    assert any(re.search(r'[\]}] convolution\(', head)
               for head, _ in ops)
    for head, line in ops:
        kind = re.search(r'[\]})] (scatter|gather)\(', head)
        if kind:
            # the row gathers of a piece and the bucket edges'
            # searchsorted: a few thousand indices at most
            shape = re.match(r'\(?\w+\[([\d,]*)\]', head).group(1)
            assert np.prod([int(d) for d in shape.split(',') if d]) \
                < 10 ** 5, line
    return ops


def _total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)


def test_served_fftpower_program_fits_and_matches_the_plan(one_chip):
    # the program AnalysisServer builds for a 1-device lane: vmap over
    # a (B,) seed array, B = 1 for requests waited in turn.  It used to
    # need 16.5 GB (parallel/dfft.py _fft_operand)
    from nbodykit_tpu.pmesh import memory_plan
    from nbodykit_tpu.serve import AnalysisRequest
    from nbodykit_tpu.serve.scheduler import _build_single
    req = AnalysisRequest(algorithm='FFTPower', nmesh=NMESH, npart=NPART)
    single = _build_single(req, _pm(NMESH))
    seeds = jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=one_chip)
    compiled = _compile(jax.vmap(single), seeds)
    need = _total_bytes(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM
    # admission and the compiler must agree: the compiler may not need
    # more than the plan's peak plus its 15% margin
    plan = memory_plan(NMESH, NPART, hbm_bytes=V5E_HBM)
    assert plan['fits']
    assert need <= plan['peak_bytes'] / 0.85, (need, plan['peak_bytes'])
    # the binning sums are matrix products: the two scatter-adds over
    # the 512 x 512 x 257 modes (0.59 s each a request on the chip) and
    # the flat index they took are gone
    hlo = compiled.as_text()
    binning = [line for line in hlo.splitlines()
               if 'nbk.fftpower.binning.hist' in line]
    assert any(' convolution(' in line for line in binning)
    assert not any('scatter' in line for line in hlo.splitlines()
                   if 'nbk.fftpower.binning' in line)
    assert 's32[%d]' % (NMESH * NMESH * (NMESH // 2 + 1)) not in hlo
    # and so is the paint (PR 33): one sort with the payload in it,
    # row gathers, per-tile products.  The eight scatters of 1e7 and
    # their eight sorts were 0.886 s of a 1.01 s request; under vmap
    # the catalogs take turns, so no sort is batched
    _paint_ops(compiled)
    assert 'f32[%d]' % NMESH ** 3 not in hlo        # the flat mesh
    assert '[1,%d]' % NPART not in hlo
    # what the program held with the scatter paint in it (PR 32): the
    # served cell's peak_hbm_gb is the loaded programs' text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes <= 2_149_835_264, m.temp_size_in_bytes
    assert m.generated_code_size_in_bytes <= 68_154_368, \
        m.generated_code_size_in_bytes


@pytest.mark.parametrize('op', ['r2c', 'c2r'])
def test_eager_fft_at_512(one_chip, op):
    pm = _pm(NMESH)
    if op == 'r2c':
        x = jax.ShapeDtypeStruct((NMESH,) * 3, jnp.float32,
                                 sharding=one_chip)
        compiled = _compile(pm.r2c, x)
    else:
        y = jax.ShapeDtypeStruct((NMESH, NMESH, NMESH // 2 + 1),
                                 jnp.complex64, sharding=one_chip)
        compiled = _compile(pm.c2r, y)
    assert _total_bytes(compiled) < 0.25 * V5E_HBM


@pytest.mark.parametrize('resampler, box', [
    ('cic', 1000.0),
    # the randoms of the survey cell: 27 deposits a particle for CIC's
    # 8, each with the particle's own signed weight
    ('tsc', 2550.0)])
def test_tile_paint_1e7_into_512(one_chip, resampler, box):
    pm = _pm(NMESH, box=box)
    pos = jax.ShapeDtypeStruct((NPART, 3), jnp.float32,
                               sharding=one_chip)
    mass = jax.ShapeDtypeStruct((NPART,), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda p, m: pm.paint(p, m, resampler=resampler), pos, mass)
    _paint_ops(compiled)
    # the padded mesh beside the field and the sorted payload: less
    # than the scatter paint's flat mesh and its sorts' buffers
    # (1.06 GB of temporaries for CIC)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9e9
    assert _total_bytes(compiled) < 0.25 * V5E_HBM


#: temp_size_in_bytes of the lab cell's binning program at 512^3 with
#: jnp.digitize's binary search in it (PR 25, the parent of PR 26)
LAB_BINNING_TEMP_PR25 = 1_612_483_584
#: the same of the other cases at PR 35, whose hist2d_mxu stored a
#: bf16[131072, 2 * nw * NB] block of one-hot columns a chunk
BINNING_TEMP_PR35 = {(1, 'lab'): LAB_BINNING_TEMP_PR25,
                     (1, 'survey'): 1_611_354_624,
                     (1, 'poles'): 1_612_386_816,
                     (4, 'poles'): 2_686_515_712}


@pytest.mark.parametrize('chips, cell', [
    pytest.param(1, 'poles', marks=pytest.mark.slow), (4, 'poles'),
    (1, 'lab'), (1, 'survey')])
def test_fftpower_binning_program(one_chip, four_chips, monkeypatch,
                                  chips, cell):
    # the (k, mu) binning program FFTPower(mode='2d', ...) jits: taken
    # from project_to_basis's builder at the point where it would be
    # jitted and kept, with the MXU histogram it uses on a TPU. 'poles': Nmu=5, poles=[0, 2,
    # 4] at 512^3 on one chip, and at 1024^3 as the shard_map over four
    # (where the first four-chip run found a replicated loop carry).
    # 'lab': the edges of the benchmark's desi_like_n512.lab cell
    # (BoxSize 5000, kmin 0.001, Nmu 10, no poles).  'survey': those of
    # boss_like_n512.convpower's three binnings (box 2550, dk 0.005,
    # one mu bin: 128 x 3 bins)
    from nbodykit_tpu.algorithms import fftpower
    from nbodykit_tpu.base.mesh import Field
    from nbodykit_tpu.ops.histogram import mxu_split
    from nbodykit_tpu.parallel.runtime import AXIS

    class Taken(Exception):
        pass

    def take(fn, label=None, **kw):
        raise Taken(fn, label)

    monkeypatch.setattr(fftpower, 'instrumented_jit', take)
    box, kmin, nmu, poles, dk = {
        'lab': (5000.0, 0.001, 10, [], None),
        'survey': (2550.0, 0.0, 1, [], 0.005),
        'poles': (1000.0, 0.0, 5, [0, 2, 4], None)}[cell]
    if chips == 1:
        nmesh, pm, sharding = NMESH, _pm(NMESH, box=box), one_chip
    else:
        nmesh, pm = 1024, _pm(1024, comm=four_chips)
        sharding = NamedSharding(four_chips, P(AXIS, None, None))
    value = jax.ShapeDtypeStruct((nmesh, nmesh, nmesh // 2 + 1),
                                 jnp.complex64, sharding=sharding)
    # FFTPower.run's own edges for that kmin, dk and Nmu
    dk = dk or 2 * np.pi / box
    edges = [np.arange(kmin, np.pi * nmesh / box + dk / 2, dk),
             np.linspace(-1, 1, nmu + 1)]
    # the key reads the value's shape and dtype alone; a builder that
    # raises keeps nothing
    kept = fftpower._binning_program.cache_info().currsize
    with pytest.raises(Taken) as got:
        fftpower.project_to_basis(Field(value, pm, 'complex'), edges,
                                  poles=poles)
    fn, label = got.value.args
    assert label == 'fftpower.binning'
    assert fftpower._binning_program.cache_info().currsize == kept
    compiled = _compile(fn, value)
    text = compiled.as_text()
    assert _total_bytes(compiled) < 0.25 * V5E_HBM
    # the bin index is a compare-and-count (ops.histogram.
    # edge_count_index): a binary search would show as a gather in a
    # while, 5.0 s of a 6.6 s lab call on the chip (PERF.md, PR 25)
    assert ' gather(' not in text
    # the sums are one matrix product a chunk of rows, taken as the
    # rows lie, its shape mxu_split's: no block of one-hot columns is
    # concatenated and stored first (0.053 s of the lab call's 0.122 s
    # of binning, twice the product; PERF.md, PR 36), no stream is
    # flattened into chunks of 131072
    NA, NB = len(edges[0]) + 1, nmu + 2
    nstreams = 3 + 2 * max(len(poles), 1)
    rows, cols = mxu_split(NA, NB, 2 * nstreams - 1)
    convs = [re.search(r'f32\[([\d,]+)\]\S* convolution\(', line)
             for line in text.splitlines()
             if 'nbk.fftpower.binning.hist' in line]
    # the compiler may keep the columns as [parts, 8]
    shapes = [[int(d) for d in m.group(1).split(',') if d != '1']
              for m in convs if m]
    assert [s for s in shapes if s[-1] == rows
            and np.prod(s[:-1]) == cols], (shapes, rows, cols)
    assert '[131072' not in text
    assert not [line for line in text.splitlines()
                if ' concatenate(' in line
                and re.search(r'\[\d{5,}', line.split(' concatenate(')[0])]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= BINNING_TEMP_PR35[chips, cell], temp


def test_pallas_deposit_kernel_at_512(one_chip):
    # the shapes paint_local_mxu hands the kernel at 512^3 / 1e7
    # (rb = cb = 8): nty = 64 tiles, one piece of 256 rows a call
    from nbodykit_tpu.ops.paint_pallas import deposit_blocks_pallas
    nty, npieces, ck = 64, 1, 256
    s = jax.ShapeDtypeStruct((nty, npieces, ck), jnp.float32,
                             sharding=one_chip)
    txi = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fn(txi, sx, sy, sz, sm):
        return deposit_blocks_pallas(
            txi, sx, sy, sz, sm, resampler='cic', rb=8, cb=8,
            n0l=NMESH, p0=NMESH, N1=NMESH, N2=NMESH, origin=0,
            dtype=jnp.float32)
    compiled = _compile(fn, txi, s, s, s, s)
    assert 'tpu_custom_call' in compiled.as_text()


def test_pallas_radix_kernel_at_1e7(one_chip):
    # stable_digit_dest's counting pass over the 1e7 bucket keys of the
    # 512^3 mxu paint: 65 * 64 + 1 buckets sort in two passes over
    # base-65 digits
    from nbodykit_tpu.ops.radix_pallas import pass_rank_hist_pallas
    digit = jax.ShapeDtypeStruct((NPART,), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda d: pass_rank_hist_pallas(d, 65), digit)
    assert 'tpu_custom_call' in compiled.as_text()


@pytest.mark.slow
def test_mxu_paint_with_pallas_deposit_at_512(one_chip):
    # the kernel where the paint really calls it
    from nbodykit_tpu import set_options
    pm = _pm(NMESH)
    pos = jax.ShapeDtypeStruct((NPART, 3), jnp.float32,
                               sharding=one_chip)
    with set_options(paint_method='mxu', paint_deposit='pallas'):
        compiled = _compile(
            lambda p: pm.paint(p, 1.0, resampler='cic',
                               return_dropped=True), pos)
    assert 'tpu_custom_call' in compiled.as_text()
    assert _total_bytes(compiled) < 0.5 * V5E_HBM


def test_slab_rfftn_1024_on_four_chips(four_chips):
    from nbodykit_tpu.parallel.runtime import AXIS
    pm = _pm(1024, comm=four_chips)
    x = jax.ShapeDtypeStruct(
        (1024,) * 3, jnp.float32,
        sharding=NamedSharding(four_chips, P(AXIS, None, None)))
    compiled = _compile(pm.r2c, x)
    assert 'all-to-all' in compiled.as_text()
    # per device: a quarter of the 4.3 GB field and of its spectrum,
    # plus workspace
    assert _total_bytes(compiled) < 0.5 * V5E_HBM


@pytest.mark.parametrize('nmesh,chips', [(NMESH, 1), (1024, 4)])
def test_cross_power_program_holds_one_field_of_its_own(
        one_chip, four_chips, nmesh, chips):
    # the lab call's 3-D power as one program: its output and the
    # re/im planes the TPU splits a complex field into, where the ops
    # one by one left four fields; on a slab mesh every device keeps
    # to its rows (clearing the DC mode moves nothing)
    from nbodykit_tpu.algorithms.fftpower import _cross_power
    from nbodykit_tpu.parallel.runtime import AXIS
    rows = one_chip if chips == 1 else \
        NamedSharding(four_chips, P(AXIS, None, None))
    c = jax.ShapeDtypeStruct((nmesh, nmesh, nmesh // 2 + 1),
                             jnp.complex64, sharding=rows)
    v = jax.ShapeDtypeStruct((), jnp.float32)
    compiled = _cross_power._jitted.lower(c, c, v).compile()
    field = 8 * nmesh * nmesh * (nmesh // 2 + 1) // chips
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes <= 1.001 * field
    assert m.temp_size_in_bytes <= 2.001 * field
    text = compiled.as_text()
    assert not any(op in text for op in (
        'all-gather', 'all-to-all', 'all-reduce', 'collective-permute'))


@pytest.mark.slow
def test_particle_exchange_1e7_on_four_chips(four_chips):
    # the all-to-all that routes 1e7 particles to their slabs, at the
    # ceil(N / P) capacity a traced caller gets
    from nbodykit_tpu.parallel.exchange import exchange_by_dest
    from nbodykit_tpu.parallel.runtime import AXIS
    rows = NamedSharding(four_chips, P(AXIS))
    dest = jax.ShapeDtypeStruct((NPART,), jnp.int32, sharding=rows)
    pos = jax.ShapeDtypeStruct(
        (NPART, 3), jnp.float32,
        sharding=NamedSharding(four_chips, P(AXIS, None)))
    mass = jax.ShapeDtypeStruct((NPART,), jnp.float32, sharding=rows)
    compiled = _compile(
        lambda d, p, m: exchange_by_dest(d, [p, m], four_chips,
                                         NPART // 4), dest, pos, mass)
    assert 'all-to-all' in compiled.as_text()
    assert _total_bytes(compiled) < 0.25 * V5E_HBM


#: the four-chip cell: desi_like as published, and the capacity its
#: 1e7 particles take on every seed (PERF.md, PR 27)
CELL_NMESH, CELL_CAPACITY = 1024, 664063


@pytest.mark.parametrize('site', ['exchange', 'paint.slab'])
def test_staged_exchange_and_paint_of_the_four_chip_cell(four_chips,
                                                         site):
    # the programs the eager four-chip lab call launches for its
    # exchange and its paint, as pmesh.paint fetches them: each has to
    # fit beside the 4.29 GB field's quarter, with no temporary a
    # staged program might add (PR 22 met a 32x padded one and a
    # replicated loop carry when eager code first became programs)
    from nbodykit_tpu import _global_options
    from nbodykit_tpu.parallel.exchange import _exchange_programs
    from nbodykit_tpu.parallel.runtime import AXIS
    from nbodykit_tpu.pmesh import _slab_paint_programs
    rows = NamedSharding(four_chips, P(AXIS))
    rows3 = NamedSharding(four_chips, P(AXIS, None))
    field = 4 * CELL_NMESH ** 3 // 4
    if site == 'exchange':
        raw, _ = _exchange_programs(four_chips, CELL_CAPACITY, 0.0,
                                    (2, 1), True)
        args = [jax.ShapeDtypeStruct((NPART,), jnp.int32, sharding=rows),
                jax.ShapeDtypeStruct((NPART, 3), jnp.float32,
                                     sharding=rows3),
                jax.ShapeDtypeStruct((NPART,), jnp.float32,
                                     sharding=rows)]
        collective = 'all-to-all'
        limit = 0.02 * V5E_HBM          # 3 x 45 MB of buffers a device
    else:
        cfg = _global_options
        assert cfg['paint_method'] == 'mxu'
        raw, _ = _slab_paint_programs(
            four_chips, (CELL_NMESH,) * 3, 'cic', cfg['paint_method'],
            cfg['paint_chunk_size'], cfg['paint_order'],
            cfg['paint_deposit'], cfg['paint_streams'],
            jnp.dtype('f4'), jnp.dtype('f4'), True)
        slots = 16 * CELL_CAPACITY
        args = [jax.ShapeDtypeStruct((slots, 3), jnp.float32,
                                     sharding=rows3),
                jax.ShapeDtypeStruct((slots,), jnp.float32,
                                     sharding=rows),
                jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=rows)]
        # the halo-extended slab (256 + 2 x 2 rows), flat as the
        # scatters fill it, as a block, and the slab cut from it
        collective = 'collective-permute'       # halo_add
        limit = 3 * 4 * 260 * CELL_NMESH ** 2
    compiled = _compile(raw, *args)
    assert collective in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= limit
    assert _total_bytes(compiled) + field < V5E_HBM


#: the survey cell: boss_like's box after FKPCatalog pads the randoms'
#: extent by 2% (PERF.md, PR 32)
SURVEY_BOX = 2550.0


def test_convpower_ell4_program_nine_transforms_at_512(one_chip):
    # the hexadecapole of the survey call as ConvolvedFFTPower fetches
    # it: nine Ylm-weighted r2c of 512^3 and their accumulation in one
    # program.  Left to itself the compiler keeps all nine transforms'
    # workspaces alive: 14.5 GB of temporaries, which compiles and then
    # cannot run beside the density and A_0 (PR 22 met a 32x padded
    # temporary between paint and r2c at this very mesh).  Taken one
    # after the other they need 4.0 GB: one transform's 1.9, A_4, the
    # weighted density and the three unit-vector fields
    from nbodykit_tpu.algorithms.convpower.fkp import _ell_program
    prog = _ell_program(4, (NMESH,) * 3, (SURVEY_BOX,) * 3,
                        '<f4', None, 'tsc', False, False)
    dens = jax.ShapeDtypeStruct((NMESH,) * 3, jnp.float32,
                                sharding=one_chip)
    origin = jax.ShapeDtypeStruct((3,), jnp.float32, sharding=one_chip)
    compiled = prog._jitted.lower(dens, origin).compile()
    field = 4 * NMESH ** 3
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes <= 1.01 * field
    assert m.temp_size_in_bytes < 0.3 * V5E_HBM, m.temp_size_in_bytes
    text = compiled.as_text()
    for name in ('nbk.convpower.ylm', 'nbk.fft.r2c',
                 'nbk.fftpower.transfer'):
        assert name in text, name


def test_paircount_tiles_program_at_the_cell_size(one_chip):
    # the pair-counting cell's one program (mr19_like: 1.2e6 points in
    # a 420 box, 14 log bins to 25; PR 35): under nbk.paircount one
    # sort of the point count with the coordinates inside it, no
    # scatter, and no gather whose index count grows with the
    # candidates (the searches of the run edges and the block table:
    # thousands of indices; the run tables: 27 entries a block; the
    # row reads of a piece: 256 blocks x 4 rows of 3 x 128 coordinates)
    from nbodykit_tpu.algorithms.pair_counters import core
    n, box = 1200000, 420.0
    edges = np.logspace(np.log10(0.1), np.log10(25.0), 15)
    work_box, redges, rmax, nb2, periodic = core._mode_setup(
        box, edges, '1d', None, None, True)
    ncell = core._grid_cells(work_box, rmax, n)
    assert ncell == (16, 16, 16)
    core._tile_program.cache_clear()
    program = core._tile_program(
        None, True, False, False, ncell, tuple(work_box), periodic,
        tuple(redges ** 2), '1d', nb2, None, 2, 'axis', (0.0,) * 3, True,
        'float32')
    try:
        compiled = program._jitted.lower(jax.ShapeDtypeStruct(
            (n, 3), jnp.float32, sharding=one_chip)).compile()
    finally:
        core._tile_program.cache_clear()
    lines = [line for line in compiled.as_text().splitlines()
             if 'nbk.paircount' in line and ' = ' in line]
    heads = [line.split(' = ', 1)[1].split('metadata')[0]
             for line in lines]
    sorts = [h for h in heads if re.search(r'\) sort\(', h)]
    assert len(sorts) == 1, sorts
    # cell id, x, y, z (and the iota that makes the sort stable)
    assert sorts[0].split(' sort(')[0].count('[%d]' % n) in (4, 5), sorts[0]
    assert not any(re.search(r'[\]})] scatter\(', h) for h in heads)
    gathers = [h for h in heads if re.search(r'[\]})] gather\(', h)]
    assert gathers
    for h in gathers:
        shape = re.match(r'\(?\w+\[([\d,]*)\]', h).group(1)
        assert np.prod([int(d) for d in shape.split(',') if d]) \
            < 10 ** 6, h
    # the catalog (14.4 MB), its sorted copy and the run tables; the
    # tiles are reduced in one pass and never stored: 23 MB when this
    # was written
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 64 * 2 ** 20, m.temp_size_in_bytes
    assert _total_bytes(compiled) < 0.01 * V5E_HBM


@pytest.mark.parametrize('program', ['fields', 'sample'])
def test_lognormal_data_phase_programs_at_512(one_chip, program):
    # the lognormal cell's data phase (desi_like_lognormal_n512, PR 39):
    # the fields program (white noise, delta_k and psi_k, four c2r, the
    # rate and its Poisson draw) and the sample program at the cell's
    # rung, with its 1.34e8 cells: no scatter of a cell's update, no
    # gather of a row's index but the one row read
    from nbodykit_tpu import mockmaker
    box, nbar = 5000.0, 8e-5
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    field = jax.ShapeDtypeStruct((NMESH,) * 3, jnp.float32,
                                 sharding=one_chip)
    if program == 'fields':
        mockmaker._fields_program.cache_clear()
        try:
            prog, _ = mockmaker._fields_program(
                (NMESH,) * 3, (box,) * 3, 'float32', None, False, 2.0,
                nbar, False, False)
            amplitude = jax.ShapeDtypeStruct(
                (NMESH, NMESH, NMESH // 2 + 1), jnp.float32,
                sharding=one_chip)
            compiled = jax.jit(prog).lower(key, amplitude).compile()
        finally:
            mockmaker._fields_program.cache_clear()
        names = ('nbk.lognormal.fields', 'nbk.fft.r2c', 'nbk.fft.c2r')
    else:
        rung, _ = mockmaker.sample_rung(nbar * box ** 3, 10 ** 7)
        mockmaker._sample_program.cache_clear()
        try:
            prog, _ = mockmaker._sample_program((NMESH,) * 3, (box,) * 3,
                                                None, rung)
            counts = jax.ShapeDtypeStruct((NMESH,) * 3, jnp.int32,
                                          sharding=one_chip)
            scalar = jax.ShapeDtypeStruct((), jnp.float32,
                                          sharding=one_chip)
            total = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
            compiled = jax.jit(prog).lower(
                key, counts, total, [field] * 3, scalar, scalar).compile()
        finally:
            mockmaker._sample_program.cache_clear()
        names = ('nbk.lognormal.sample',)
        hlo = compiled.as_text()
        looped = []
        for line in hlo.splitlines():
            kind = re.search(r'[\]})] (scatter|gather)\(',
                             line.split(' = ', 1)[-1])
            if kind and 'nbk.lognormal' in line:
                # the segments' starts, the strided read of them and the
                # row read; nothing with an index a cell
                assert '[%d]' % NMESH ** 3 not in line.split(kind.group(1))[0], line
                if '/while/' in line:
                    looped.append(line)
        # one read a row of output in the loop's body: a whole row of
        # the segments' table, 4 x 64 words
        assert len(looped) == 1 and ' gather(' in looped[0] \
            and 'slice_sizes={1,256}' in looped[0], looped
    text = compiled.as_text()
    for name in names:
        assert name in text, name
    m = compiled.memory_analysis()
    print(program, m.temp_size_in_bytes, _total_bytes(compiled))
    assert _total_bytes(compiled) < 0.6 * V5E_HBM
    if program == 'sample':
        # no more than the two-gather placement held (3,759,289,856 B
        # when this was written)
        print('sample temp bound', SAMPLE_TEMP_BYTES)
        assert m.temp_size_in_bytes <= SAMPLE_TEMP_BYTES
