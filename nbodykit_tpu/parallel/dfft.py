"""Distributed 3-D real-to-complex FFT over a 1-D (slab) or 2-D
(pencil) device mesh.

This replaces the reference's pfft/pmesh slab-decomposed MPI FFT (consumed
at nbodykit/base/mesh.py:296-304 via ``RealField.r2c``). The design is the
TPU-idiomatic analog of pfft's transposed slab algorithm:

  real field   : global (N0, N1, N2), sharded P('dev', None, None)
  complex field: global (N1, N0, N2//2+1), sharded P('dev', None, None)
                 — *transposed* layout: the leading (sharded) axis of the
                 complex field is ky, the second axis is kx. Like pfft's
                 ``transposed=True`` plan, this halves the number of
                 all-to-all passes: one per direction instead of two.

Algorithm (per device, inside shard_map; P = number of devices):

  r2c:  (N0/P, N1, N2) --rfft ax2--> (N0/P, N1, Nc)
                       --fft  ax1--> (N0/P, N1, Nc)
        --all_to_all(split ax1, concat ax0)--> (N0, N1/P, Nc)
                       --fft  ax0--> (N0, N1/P, Nc)
                       --transpose-> (N1/P, N0, Nc)

  c2r is the exact reverse.

The all_to_all rides the ICI when the mesh spans a TPU slice. Everything is
inside one jitted graph so XLA fuses the surrounding elementwise work
(window compensation, P(k) transfer, binning weights) into the FFT stages.

Hermitian compression comes for free from rfft (last axis length N2//2+1);
the double-count weights for the missing half-plane are handled at binning
time (see meshtools.py, mirroring reference nbodykit/meshtools.py:188-215).

Pencil (2-D) decomposition
--------------------------
The slab algorithm caps useful parallelism at N0 slabs and pays ONE
P-way all_to_all moving the whole N³ field across the fleet. On a 2-D
``Mesh(('x', 'y'))`` of shape (Px, Py) the field is decomposed into
(N0/Px, N1/Py, N2) *pencils* and the transpose splits in two:

  r2c:  (N0/Px, N1/Py, N2) --rfft ax2--> (., ., Nc) --pad z to %Py-->
        --a2a over 'y' (split ax2, concat ax1)--> (N0/Px, N1, Ncp/Py)
                          --fft  ax1-->
        --a2a over 'x' (split ax1, concat ax0)--> (N0, N1/Px, Ncp/Py)
                          --fft  ax0--> --transpose--> (N1/Px, N0, .)

The inner a2a stays within a 'y' group (ICI on a hybrid mesh built by
:func:`..runtime.pencil_mesh`); the outer a2a crosses 'x' groups (DCN
across slices). Each moves the field once among only Py (resp. Px)
peers, vs the slab's single P-way exchange — see docs/PERF.md "Slab vs
pencil" for the communication-volume model. The Hermitian-compressed z
axis (Nc = N2//2+1) is zero-padded to a multiple of Py before the inner
transpose; the pad columns stay exactly zero through the remaining
(linear) stages and are sliced off the output. Output layout and
normalization are identical to the slab path, so the two decompositions
are interchangeable per call. Selection is an option
(``set_options(fft_decomp='slab'|'pencil')``) read at dispatch in
:class:`dist_fft_plan`.
"""

from functools import lru_cache as _lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .runtime import AXIS, AXIS_X, AXIS_Y, default_pencil_factor, \
    is_eager, is_pencil, mesh_size, pencil_mesh
from ..diagnostics import counter, current_tracer, \
    install_compile_telemetry, instrumented_jit, scope, span, span_if

# every XLA compile triggered by the FFT paths lands in the metric
# registry (xla.compile.* / xla.cache.*) — answers "why was rep 1
# slow" from the trace alone
install_compile_telemetry()


def _fft_operand(x):
    """The real field as the r2c's operand, behind an
    ``optimization_barrier``.

    The paint kernels build the field flat and ``reshape`` it to
    (N0, N1, N2); the TPU compiler splits a length-512 rfft axis into
    [128, 4] and, when paint and transform share a program, merges
    the two reshapes into one flat -> [N0, N1, 128, 4] copy whose minor
    dimension of 4 is padded to the 128-lane tile.  libtpu 0.0.34 on
    the served 512^3 FFTPower program: ``RESOURCE_EXHAUSTED ... Used
    16.50G of 15.75G hbm ... f32[512,512,128,4]{3,2,1,0:T(8,128)} ...
    Extra memory due to padding: 15.50G (32.0x expansion)``.  Behind
    the barrier the field is materialized in its own (N0, N1, N2)
    layout first, as it is when the transform is compiled alone
    (1.5 GB of temporaries at 512^3; tests/test_tpu_compile.py holds
    both).  The barrier is the identity to autodiff and vmap."""
    return jax.lax.optimization_barrier(x)


def _fft_chunk_bytes():
    """The chunking target, the ``fft_chunk_bytes`` option (0: no
    chunking)."""
    from .. import _global_options
    return int(_global_options['fft_chunk_bytes'])


def _a2a_mode():
    """The ``a2a_compress`` wire format of the next transform: 'none'
    (f32/f64 complex payload), 'bf16' (half-width planes on the wire,
    re-widened on receipt) or 'int16' (quantized planes with
    per-source-shard scale factors).  Read here, at closure-build /
    trace time, so the compiled program carries one format."""
    from .. import _global_options
    v = _global_options['a2a_compress']
    if v in (None, False, 'none'):
        return 'none'
    return str(v)


def _a2a(y, axis_name, split_axis, concat_axis, nsplit, mode='none'):
    """One FFT transpose collective with an optional compressed wire
    format (ROADMAP item 5: the distributed FFT is all_to_all-bound,
    so halving the bytes on the wire halves the measured ceiling).

    The transform stages COMPUTE at full width either side of this
    call; compression exists only between the split and the concat:

    - ``'bf16'``: the complex payload is carried as a stacked
      (real, imag) plane pair cast to bfloat16 — half the bytes — and
      re-widened immediately on the receiving side (the literal
      ``.astype`` on the collective is the NBK701 contract).
    - ``'int16'``: the plane pair is quantized to int16 against ONE
      scalar scale per source shard (max|planes|/32767, clamped away
      from zero); the scale rides the SAME all_to_all payload —
      bitcast to two int16 lanes appended along the concat axis — so
      each received block carries its sender's scale and no second
      collective is needed.  Half the bytes of 'bf16's exponent-heavy
      format spent on mantissa instead — better for fields with
      narrow dynamic range per shard, worse across decades.

    ``nsplit`` is the group size of ``axis_name`` (the number of
    blocks the concat axis is composed of — slab: P, pencil inner:
    Py, pencil outer: Px).

    ``mode`` is static configuration resolved at closure-build time
    (:func:`_a2a_mode`), so the branch below is compiled away; every
    mode emits exactly ONE all_to_all and nothing else — the
    collective program is identical on every arm and every rank
    (NBK103 by construction)."""
    if mode == 'bf16':
        out = _a2a_bf16(y, axis_name, split_axis, concat_axis, nsplit)
    elif mode == 'int16':
        out = _a2a_int16(y, axis_name, split_axis, concat_axis,
                         nsplit)
    else:
        out = _a2a_plain(y, axis_name, split_axis, concat_axis,
                         nsplit)
    return out


def _a2a_plain(y, axis_name, split_axis, concat_axis, nsplit):
    return jax.lax.all_to_all(y, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def _a2a_bf16(y, axis_name, split_axis, concat_axis, nsplit):
    counter('fft.trace.a2a_bf16').add(1)
    planes = jnp.stack([jnp.real(y), jnp.imag(y)])
    # the stacked plane axis is leading: split/concat shift by 1.
    # The wire carries bf16; the re-widen lands on f32 (the bf16
    # payload holds no more precision than f32 can represent, so an
    # f64 input loses nothing beyond what the wire already dropped)
    narrow = planes.astype(jnp.bfloat16)
    wide = jax.lax.all_to_all(
        narrow, axis_name, split_axis=split_axis + 1,
        concat_axis=concat_axis + 1, tiled=True).astype(jnp.float32)
    return jax.lax.complex(wide[0], wide[1]).astype(y.dtype)


def _a2a_int16(y, axis_name, split_axis, concat_axis, nsplit):
    counter('fft.trace.a2a_int16').add(1)
    planes = jnp.stack([jnp.real(y), jnp.imag(y)])
    wdt = planes.dtype
    # one scalar scale per source shard, computed and applied in f32
    # so the wire encoding is exact regardless of x64
    scale = jnp.maximum(jnp.max(jnp.abs(planes)),
                        jnp.asarray(1e-30, wdt))
    scale = (scale / 32767.0).astype(jnp.float32)
    qi = jnp.round(planes / scale.astype(wdt)).astype(jnp.int16)
    # the scale rides the payload: bitcast f32 -> 2 int16 lanes,
    # appended along the concat axis of every destination block, so
    # one all_to_all moves data AND scales (no trailing all_gather)
    sa, ca = split_axis + 1, concat_axis + 1
    scode = jax.lax.bitcast_convert_type(scale, jnp.int16)
    lane = jnp.reshape(scode, (1,) * ca + (2,)
                       + (1,) * (qi.ndim - ca - 1))
    pad_shape = qi.shape[:ca] + (2,) + qi.shape[ca + 1:]
    wire = jnp.concatenate(
        [qi, jnp.broadcast_to(lane, pad_shape)], axis=ca)
    qr = jax.lax.all_to_all(wire, axis_name, split_axis=sa,
                            concat_axis=ca, tiled=True)
    # the received concat axis is nsplit sender blocks in source
    # order, each data rows then its 2-lane scale: dequantize each
    # block by its sender's scale
    m = qi.shape[ca]
    moved = jnp.moveaxis(qr, ca, 0)
    blocks = moved.reshape((nsplit, m + 2) + moved.shape[1:])
    codes = blocks[:, m:].reshape((nsplit, 2, -1))[:, :, 0]
    scales = jax.lax.bitcast_convert_type(codes, jnp.float32)
    wide = blocks[:, :m].astype(wdt) * scales.astype(wdt).reshape(
        (nsplit,) + (1,) * (blocks.ndim - 1))
    wide = jnp.moveaxis(
        wide.reshape((nsplit * m,) + moved.shape[1:]), 0, ca)
    return jax.lax.complex(wide[0], wide[1]).astype(y.dtype)


# --------------------------------------------------------------------
# tier-0 integrity guards on the a2a wire (resilience/integrity.py;
# docs/INTEGRITY.md).  An all_to_all permutes a global payload without
# changing its elements, so the globally-psummed fold sum(|Re|+|Im|)
# is wire-invariant; the compressed formats are checked
# pre-quantization vs dequantized against the budget the format
# itself implies.  All of this is OFF by default: the guard branch is
# resolved at closure-build time (integrity='off' compiles the
# identical program as before — zero added ops, bit-identical
# results) and only eager drivers compare, since a data-dependent
# raise cannot live under trace.
# --------------------------------------------------------------------

def _integrity_on():
    from ..resilience.integrity import checks_enabled
    return checks_enabled()


def _corrupt_bits():
    """Consult the ``a2a.payload`` corrupt injection point (fault
    grammar ``corrupt[:bits]``) — 0 almost always."""
    from ..resilience.faults import corrupt_spec
    return corrupt_spec('a2a.payload')


def _wire_fold(v):
    """The wire-invariant fold: sum(|Re| + |Im|) in f32 (local)."""
    return (jnp.sum(jnp.abs(jnp.real(v)).astype(jnp.float32)) +
            jnp.sum(jnp.abs(jnp.imag(v)).astype(jnp.float32)))


def _corrupt_wire(y, bits, axes):
    """Deterministically flip ``bits`` top bits of ONE global payload
    word (element [0,...] on the zero-coordinate rank).  The select is
    rank-uniform — every rank runs the same program (NBK103) and the
    where() picks the corrupted value only where every axis index is
    zero."""
    from ..resilience.integrity import corrupt_complex
    idx = sum(jax.lax.axis_index(a) for a in axes)
    return jnp.where(idx == 0, corrupt_complex(y, bits), y)


def _a2a_site(y, axis_name, split_axis, concat_axis, nsplit, mode,
              axes, check, bits):
    """One a2a with optional corruption injection and optional guard
    folds.  Returns ``(out, stats)`` where ``stats`` is None when
    unchecked, else a psummed f32 triple [pre, post, qerr]: the fold
    before the wire, the fold after (dequantized for compressed
    formats), and the summed quantization-error bound (int16's
    data-dependent scale, priced in-graph so the budget is honest).
    The guarded program emits the SAME single all_to_all plus two
    psums, identically on every rank."""
    # always a shard_map body inside a program: the scope names the
    # collective and its guard folds ``nbk.fft.a2a.<axis>`` in the HLO
    # op names
    with scope('fft.a2a.%s' % axis_name):
        # ``check``/``bits`` are host-static (checks_enabled() and the
        # consumed fault rule, identical on every rank), so the arms pick
        # ONE program uniformly  # nbkl: disable=NBK103
        if not check:
            if bits:
                y = _corrupt_wire(y, bits, axes)
            return _a2a(y, axis_name, split_axis, concat_axis, nsplit,
                        mode), None
        pre = _wire_fold(y)
        if mode == 'int16':
            # mirror _a2a_int16's per-shard scale: each dequantized plane
            # element is within scale/2 of its original, so the local fold
            # can move by at most (2 * y.size) * scale / 2
            m = jnp.maximum(jnp.max(jnp.abs(jnp.real(y))),
                            jnp.max(jnp.abs(jnp.imag(y))))
            scale = jnp.maximum(m.astype(jnp.float32),
                                jnp.float32(1e-30)) / jnp.float32(32767.0)
            qerr = jnp.float32(y.size) * scale
        else:
            qerr = jnp.float32(0)
        if bits:
            y = _corrupt_wire(y, bits, axes)
        out = _a2a(y, axis_name, split_axis, concat_axis, nsplit, mode)
        post = _wire_fold(out)
        stats = jax.lax.psum(jnp.stack([pre, post, qerr]), axes)
        return out, stats


def _a2a_verify(site, stats, mode, n):
    """Host-side comparison of one guarded a2a's psummed folds (eager
    drivers only).  bf16 widens the budget by its mantissa step; int16
    by twice the in-graph quantization bound; non-finite folds trip
    the NaN/Inf tripwire inside check_a2a."""
    import numpy as np
    from ..resilience import integrity
    pre, post, qerr = [float(v) for v in
                       np.asarray(jax.device_get(stats))]
    rel = integrity.rel_budget('float32', n)
    if mode == 'bf16':
        rel += 2.0 ** -8
    budget = (pre * rel + 2.0 * qerr) if pre == pre else float('nan')
    integrity.check_a2a(site, pre, post, budget)


def _parseval_verify(site, shape, sx, y, norm):
    """Parseval bracket for a forward rFFT (eager): the Hermitian-
    weighted power of the output must equal the input power times the
    transform's scale.  Runs at the public dist_rfftn entry so slab,
    pencil and single-device paths are all covered by one guard."""
    if norm not in (None, 'ortho'):
        return
    from ..resilience import integrity
    n2 = int(shape[2])
    p = jnp.square(jnp.abs(y).astype(jnp.float32))
    s_all = jnp.sum(p)
    # Hermitian double-count weights on the compressed z axis: the
    # iz=0 column (and iz=Nc-1 when N2 is even) appears once in the
    # full spectrum, every other column twice
    s_edge = jnp.sum(p[:, :, 0])
    if n2 % 2 == 0 and int(y.shape[2]) > 1:
        s_edge = s_edge + jnp.sum(p[:, :, -1])
    sk = float(2.0 * s_all - s_edge)
    ntot = float(shape[0]) * float(shape[1]) * float(shape[2])
    want = float(sx) * (ntot if norm is None else 1.0)
    integrity.check_close(site, sk, want,
                          integrity.rel_budget('float32', int(ntot)))


def _lowmem_step(emit, upd, slab, buf, arr, k, r, stage):
    """One eager chunk of a lowmem pass, optionally wrapped in an
    ``fft.chunk`` span.  The per-chunk wall is *dispatch* time (the
    stage programs are async); stalls show up on the chunks that fill
    the dispatch queue, and the enclosing ``fft.lowmem.*`` span has
    the true total."""
    idx = jnp.int32(k * r)
    if not emit:
        return upd(buf, slab(arr, idx), idx)
    with span('fft.chunk', stage=stage, index=k, rows=r):
        return upd(buf, slab(arr, idx), idx)


def _chunk_rows(n, bytes_per_row, target):
    """Largest divisor of ``n`` whose slab stays under ``target`` bytes.

    All-integer arithmetic (callers concretize ``target`` at the
    program-cache boundary): shapes stay static under trace."""
    r = max(1, min(n, target // max(bytes_per_row, 1)))
    while n % r:
        r -= 1
    return r


def rfftn_single_lowmem(x_box, norm=None, target=None):
    """Eager single-device 3-D rFFT that peaks at ~2 full-mesh buffers.

    The in-jit chunked transform (:func:`_rfftn_single_chunked`) keeps
    every FFT op small, but XLA double-buffers the ``fori_loop`` carry,
    so the whole program still holds ~4 full-mesh buffers — over a
    single chip's HBM for a 1024-cube next to the painted field.  Here
    the chunk loop runs in *Python* and each chunk call donates the
    accumulator, which XLA aliases in-place across call boundaries
    (guaranteed for same-shape/dtype donation, unlike a loop carry).

    ``x_box`` is a single-element list holding the real field; the
    list is emptied (ownership transfer) so the input buffer can be
    freed as soon as the first pass is done — the caller must not keep
    another reference.  The ~2-buffer peak therefore only holds when
    the WHOLE call chain relinquishes: reached via :func:`dist_rfftn`
    the public caller retains its own reference to the field, and the
    peak is ~3 full-mesh buffers (input + intermediate + output) —
    callers that need the tight contract (bench.py's staged 1024³
    path) build the box in-place and call this driver directly.
    Returns the transposed (N1, N0, Nc) layout of :func:`dist_rfftn`.
    Not traceable: call outside jit.

    This contract is MACHINE-CHECKED since nbkl v2: the linter's
    symbolic peak model (``nbodykit-tpu-lint --memory-report``)
    derives exactly 2.0 full-mesh units for this driver from the
    source — donated ``upd`` programs alias the accumulator, the
    ``del x`` ends the input's live range before pass B — and
    ``tests/test_lint_dataflow.py`` fails if an edit regresses it.
    """
    if isinstance(x_box, (list,)):
        x = x_box.pop()
    else:
        x = x_box
    if target is None:
        target = _fft_chunk_bytes() or 2 ** 31
    progs = _lowmem_programs(x.shape, str(x.dtype), norm, int(target))
    r0, r1, zeros_y, zeros_out, slab_a, upd_a, slab_b, upd_b = progs
    N0, N1, _ = x.shape

    emit = current_tracer() is not None
    counter('fft.chunks').add(N0 // r0 + N1 // r1)
    with span_if(emit, 'fft.lowmem.r2c', shape=[int(N0), int(N1)],
                 chunks=[N0 // r0, N1 // r1]):
        # pass A: rfft along z + fft along y, slab-chunked over x rows;
        # y is donated through every chunk call -> updated in place
        y = zeros_y()
        for i in range(N0 // r0):
            y = _lowmem_step(emit, upd_a, slab_a, y, x, i, r0,
                             'r2c.rfftz_ffty')
        del x  # input freed before pass B allocates its output

        # pass B: fft along x, chunked over y columns, written transposed
        out = zeros_out()
        for j in range(N1 // r1):
            out = _lowmem_step(emit, upd_b, slab_b, out, y, j, r1,
                               'r2c.fftx')
        return out


def irfftn_single_lowmem(y_box, Nmesh2, norm=None, target=None):
    """Eager inverse of :func:`rfftn_single_lowmem` (same ownership and
    peak-memory contract: pass the transposed complex field in a
    one-element list; ~2 full-mesh buffers peak)."""
    y = y_box.pop() if isinstance(y_box, list) else y_box
    if target is None:
        target = _fft_chunk_bytes() or 2 ** 31
    progs = _lowmem_inv_programs(y.shape, str(y.dtype), int(Nmesh2),
                                 norm, int(target))
    r1, r0, zeros_z, zeros_out, slab_a, upd_a, slab_b, upd_b = progs
    N1, N0, _ = y.shape

    emit = current_tracer() is not None
    counter('fft.chunks').add(N1 // r1 + N0 // r0)
    with span_if(emit, 'fft.lowmem.c2r', shape=[int(N1), int(N0)],
                 chunks=[N1 // r1, N0 // r0]):
        # pass A: undo the x-axis fft, chunked over ky rows (in-place)
        z = zeros_z()
        for j in range(N1 // r1):
            z = _lowmem_step(emit, upd_a, slab_a, z, y, j, r1,
                             'c2r.ifftx')
        del y

        # pass B: ifft over ky + irfft over kz, chunked over x rows
        out = zeros_out()
        for i in range(N0 // r0):
            out = _lowmem_step(emit, upd_b, slab_b, out, z, i, r0,
                               'c2r.iffty_irfftz')
        return out


@_lru_cache(maxsize=16)
def _lowmem_inv_programs(shape, dtype_str, Nmesh2, norm, target):
    """Jitted stage programs for :func:`irfftn_single_lowmem`."""
    N1, N0, Nc = shape
    csz = jnp.dtype(dtype_str).itemsize
    cdt = jnp.dtype(dtype_str)
    rdt = jnp.float32 if csz <= 8 else jnp.float64
    op_target = max(target // 4, 1)
    r1 = _chunk_rows(N1, N0 * Nc * csz, op_target)
    row_b = max(N1 * Nc * csz, N1 * Nmesh2 * jnp.dtype(rdt).itemsize)
    r0 = _chunk_rows(N0, row_b, op_target)

    def _upd_a(dst, s, j):
        z = jnp.zeros((), j.dtype)
        return jax.lax.dynamic_update_slice(dst, s, (z, j, z))

    def _upd_b(dst, s, i):
        z = jnp.zeros((), i.dtype)
        return jax.lax.dynamic_update_slice(dst, s, (i, z, z))

    @instrumented_jit(label='fft.lowmem.c2r.slab_a')
    def slab_a(y, j):
        z = jnp.zeros((), j.dtype)
        yc = jax.lax.dynamic_slice(y, (j, z, z), (r1, N0, Nc))
        return jnp.transpose(jnp.fft.ifft(yc, axis=1, norm=norm),
                             (1, 0, 2))

    @instrumented_jit(label='fft.lowmem.c2r.slab_b')
    def slab_b(zf, i):
        z = jnp.zeros((), i.dtype)
        sl = jax.lax.dynamic_slice(zf, (i, z, z), (r0, N1, Nc))
        return jnp.fft.irfft(jnp.fft.ifft(sl, axis=1, norm=norm),
                             n=Nmesh2, axis=2, norm=norm).astype(rdt)

    zeros_z = jax.jit(lambda: jnp.zeros((N0, N1, Nc), cdt))
    zeros_out = jax.jit(lambda: jnp.zeros((N0, N1, Nmesh2), rdt))
    return (r1, r0, zeros_z, zeros_out, slab_a,
            instrumented_jit(_upd_a, label='fft.lowmem.c2r.upd',
                             donate_argnums=(0,)), slab_b,
            instrumented_jit(_upd_b, label='fft.lowmem.c2r.upd',
                             donate_argnums=(0,)))


@_lru_cache(maxsize=16)
def _lowmem_programs(shape, dtype_str, norm, target):
    """Jitted stage programs for :func:`rfftn_single_lowmem`, cached per
    (shape, dtype, norm, target) so repeated transforms re-use the
    compiled executables instead of re-tracing every call.

    Every step is a jitted program and slice starts are traced, so
    each program compiles exactly once.
    """
    N0, N1, N2 = shape
    Nc = N2 // 2 + 1
    itemsize = jnp.dtype(dtype_str).itemsize
    cdt = jnp.complex64 if itemsize <= 4 else jnp.complex128
    csz = jnp.dtype(cdt).itemsize
    op_target = max(target // 4, 1)
    r0 = _chunk_rows(N0, N1 * Nc * csz, op_target)
    r1 = _chunk_rows(N1, N0 * Nc * csz, op_target)

    def _upd(dst, s, i):
        z = jnp.zeros((), i.dtype)
        return jax.lax.dynamic_update_slice(dst, s, (i, z, z))

    @instrumented_jit(label='fft.lowmem.r2c.slab_a')
    def slab_a(x, i):
        z = jnp.zeros((), i.dtype)
        xc = jax.lax.dynamic_slice(x, (i, z, z), (r0, N1, N2))
        return jnp.fft.fft(jnp.fft.rfft(xc, axis=2, norm=norm),
                           axis=1, norm=norm).astype(cdt)

    @instrumented_jit(label='fft.lowmem.r2c.slab_b')
    def slab_b(y, j):
        z = jnp.zeros((), j.dtype)
        yc = jax.lax.dynamic_slice(y, (z, j, z), (N0, r1, Nc))
        return jnp.transpose(jnp.fft.fft(yc, axis=0, norm=norm),
                             (1, 0, 2))

    zeros_y = jax.jit(lambda: jnp.zeros((N0, N1, Nc), cdt))
    zeros_out = jax.jit(lambda: jnp.zeros((N1, N0, Nc), cdt))
    return (r0, r1, zeros_y, zeros_out, slab_a,
            instrumented_jit(_upd, label='fft.lowmem.r2c.upd',
                             donate_argnums=(0,)), slab_b,
            instrumented_jit(_upd, label='fft.lowmem.r2c.upd',
                             donate_argnums=(0,)))


@_lru_cache(maxsize=16)
def _lowmem_c2c_programs(shape, dtype_str, inverse, norm, target):
    """Jitted stage programs for :func:`fftn_c2c_single_lowmem` (same
    caching/donation rationale as :func:`_lowmem_programs`)."""
    dt = jnp.dtype(dtype_str)
    cdt = jnp.result_type(dt, jnp.complex64)
    csz = jnp.dtype(cdt).itemsize
    op_target = max(target // 4, 1)
    if inverse:
        N1, N0, N2 = shape
    else:
        N0, N1, N2 = shape
    r0 = _chunk_rows(N0, N1 * N2 * csz, op_target)
    r1 = _chunk_rows(N1, N0 * N2 * csz, op_target)
    fft = jnp.fft.ifft if inverse else jnp.fft.fft

    def _upd_row(dst, s, i):
        z = jnp.zeros((), i.dtype)
        return jax.lax.dynamic_update_slice(dst, s, (i, z, z))

    def _upd_col(dst, s, j):
        z = jnp.zeros((), j.dtype)
        return jax.lax.dynamic_update_slice(dst, s, (z, j, z))

    if not inverse:
        # pass A: fft z + fft y over x-slabs (in place); pass B: fft x
        # over y-slabs of the intermediate, written transposed
        @instrumented_jit(label='fft.lowmem.c2c.slab_a')
        def slab_a(x, i):
            z = jnp.zeros((), i.dtype)
            sl = jax.lax.dynamic_slice(x, (i, z, z), (r0, N1, N2))
            return fft(fft(sl, axis=2, norm=norm),
                       axis=1, norm=norm).astype(cdt)

        @instrumented_jit(label='fft.lowmem.c2c.slab_b')
        def slab_b(y, j):
            z = jnp.zeros((), j.dtype)
            sl = jax.lax.dynamic_slice(y, (z, j, z), (N0, r1, N2))
            return jnp.transpose(fft(sl, axis=0, norm=norm), (1, 0, 2))

        zeros_mid = jax.jit(lambda: jnp.zeros((N0, N1, N2), cdt))
        zeros_out = jax.jit(lambda: jnp.zeros((N1, N0, N2), cdt))
        loops = (N0 // r0, r0, N1 // r1, r1)
        upd_a, upd_b = _upd_row, _upd_row
        stages = ('c2c.fftz_ffty', 'c2c.fftx')
    else:
        # pass A: undo the x-axis fft (axis 1 of the transposed
        # layout) over ky-slabs, written back in (x, ky, kz) order;
        # pass B: ifft y + ifft z over x-slabs
        @instrumented_jit(label='fft.lowmem.c2c.islab_a')
        def slab_a(y, j):
            z = jnp.zeros((), j.dtype)
            sl = jax.lax.dynamic_slice(y, (j, z, z), (r1, N0, N2))
            return jnp.transpose(fft(sl, axis=1, norm=norm),
                                 (1, 0, 2)).astype(cdt)

        @instrumented_jit(label='fft.lowmem.c2c.islab_b')
        def slab_b(zf, i):
            z = jnp.zeros((), i.dtype)
            sl = jax.lax.dynamic_slice(zf, (i, z, z), (r0, N1, N2))
            return fft(fft(sl, axis=1, norm=norm), axis=2, norm=norm)

        zeros_mid = jax.jit(lambda: jnp.zeros((N0, N1, N2), cdt))
        zeros_out = jax.jit(lambda: jnp.zeros((N0, N1, N2), cdt))
        loops = (N1 // r1, r1, N0 // r0, r0)
        upd_a, upd_b = _upd_col, _upd_row
        stages = ('c2c.ifftx', 'c2c.iffty_ifftz')
    return (loops, stages, zeros_mid, zeros_out, slab_a,
            instrumented_jit(upd_a, label='fft.lowmem.c2c.upd',
                             donate_argnums=(0,)), slab_b,
            instrumented_jit(upd_b, label='fft.lowmem.c2c.upd',
                             donate_argnums=(0,)))


def fftn_c2c_single_lowmem(x_box, inverse=False, norm=None,
                           target=None):
    """Eager single-device c2c 3-D FFT peaking at ~2 full-mesh buffers
    (same ownership contract as :func:`rfftn_single_lowmem`: pass the
    field in a one-element list, which is emptied).  Forward maps
    (N0, N1, N2) -> transposed (N1, N0, N2); inverse is the exact
    reverse.  This is the OOM-ladder rung the resilience Supervisor
    degrades convpower's odd-multipole Ylm transforms onto (see
    docs/RESILIENCE.md).  Not traceable: call outside jit."""
    x = x_box.pop() if isinstance(x_box, list) else x_box
    if target is None:
        target = _fft_chunk_bytes() or 2 ** 31
    progs = _lowmem_c2c_programs(x.shape, str(x.dtype), bool(inverse),
                                 norm, int(target))
    loops, stages, zeros_mid, zeros_out, slab_a, upd_a, slab_b, upd_b \
        = progs
    nA, rA, nB, rB = loops

    emit = current_tracer() is not None
    counter('fft.chunks').add(nA + nB)
    with span_if(emit, 'fft.lowmem.c2c', inverse=bool(inverse),
                 shape=[int(s) for s in x.shape], chunks=[nA, nB]):
        mid = zeros_mid()
        for k in range(nA):
            mid = _lowmem_step(emit, upd_a, slab_a, mid, x, k, rA,
                               stages[0])
        del x  # input freed before pass B allocates its output

        out = zeros_out()
        for k in range(nB):
            out = _lowmem_step(emit, upd_b, slab_b, out, mid, k, rB,
                               stages[1])
        return out


def _rfftn_single_chunked(x, norm, target):
    """Single-device 3-D rFFT as three slab-chunked 1-D passes.

    A single FFT op over a multi-GB buffer carries a workspace of
    three times its input (compiled for a v5e, ``jnp.fft.rfftn`` at
    512^3 books 1.5 GB of temporaries for a 0.5 GB field), so beyond
    ``set_options(fft_chunk_bytes=...)`` the transform runs per axis
    over slabs of ~target/4 bytes inside ``fori_loop``.  At these sizes
    the FFT is HBM-bound either way; the extra pass over the array is
    the only cost.  Returns the transposed (N1, N0, Nc) layout like the
    multi-device path.
    """
    N0, N1, N2 = x.shape
    Nc = N2 // 2 + 1
    cdt = jnp.complex64 if x.dtype.itemsize <= 4 else jnp.complex128
    csz = jnp.dtype(cdt).itemsize
    op_target = max(target // 4, 1)

    # pass A: rfft along z + fft along y, slab-chunked over x
    r0 = _chunk_rows(N0, N1 * Nc * csz, op_target)
    # '.trace.': bumped once per compilation of this program, not per
    # execution (the loop is in-graph; see diagnostics/metrics.py)
    counter('fft.trace.chunks').add(N0 // r0)
    y = jnp.zeros((N0, N1, Nc), cdt)

    def body_a(i, y):
        sl = jax.lax.dynamic_slice(x, (i * r0, 0, 0), (r0, N1, N2))
        s = jnp.fft.fft(jnp.fft.rfft(sl, axis=2, norm=norm),
                        axis=1, norm=norm).astype(cdt)
        return jax.lax.dynamic_update_slice(y, s, (i * r0, 0, 0))

    y = jax.lax.fori_loop(0, N0 // r0, body_a, y)

    # pass B: fft along x, chunked over y, written transposed
    r1 = _chunk_rows(N1, N0 * Nc * csz, op_target)
    out = jnp.zeros((N1, N0, Nc), cdt)

    def body_b(j, out):
        sl = jax.lax.dynamic_slice(y, (0, j * r1, 0), (N0, r1, Nc))
        s = jnp.transpose(jnp.fft.fft(sl, axis=0, norm=norm), (1, 0, 2))
        return jax.lax.dynamic_update_slice(out, s, (j * r1, 0, 0))

    return jax.lax.fori_loop(0, N1 // r1, body_b, out)


def _irfftn_single_chunked(y, Nmesh2, norm, target):
    """Inverse of :func:`_rfftn_single_chunked` (same chunking rationale)."""
    N1, N0, Nc = y.shape
    csz = jnp.dtype(y.dtype).itemsize
    rdt = jnp.float32 if csz <= 8 else jnp.float64
    op_target = max(target // 4, 1)

    # pass A: undo the x-axis fft (axis 1 of the transposed layout),
    # chunked over ky rows, written back in (x, ky, kz) order
    r1 = _chunk_rows(N1, N0 * Nc * csz, op_target)
    z = jnp.zeros((N0, N1, Nc), y.dtype)

    def body_a(j, z):
        sl = jax.lax.dynamic_slice(y, (j * r1, 0, 0), (r1, N0, Nc))
        s = jnp.transpose(jnp.fft.ifft(sl, axis=1, norm=norm), (1, 0, 2))
        return jax.lax.dynamic_update_slice(z, s, (0, j * r1, 0))

    z = jax.lax.fori_loop(0, N1 // r1, body_a, z)

    # pass B: ifft along y + irfft along z, chunked over x rows
    row_b = max(N1 * Nc * csz, N1 * Nmesh2 * jnp.dtype(rdt).itemsize)
    r0 = _chunk_rows(N0, row_b, op_target)
    out = jnp.zeros((N0, N1, Nmesh2), rdt)

    def body_b(i, out):
        sl = jax.lax.dynamic_slice(z, (i * r0, 0, 0), (r0, N1, Nc))
        s = jnp.fft.irfft(jnp.fft.ifft(sl, axis=1, norm=norm),
                          n=Nmesh2, axis=2, norm=norm)
        return jax.lax.dynamic_update_slice(out, s.astype(rdt),
                                            (i * r0, 0, 0))

    return jax.lax.fori_loop(0, N0 // r0, body_b, out)


# --------------------------------------------------------------------
# pencil (2-D) decomposition
# --------------------------------------------------------------------

#: the eager pencil path's documented peak: at most this many padded
#: complex pencil units live per device at once — stage 1's output and
#: stage 2's output, with stage 2 DONATING stage 1's intermediate
#: (``_pencil_programs`` j2).  ``pmesh.memory_plan`` prices the branch
#: with exactly this count and the smoke gate asserts it at 1024^3.
PENCIL_BUFFERS = 2


def _pencil_shape(mesh):
    """(Px, Py) of a 2-D pencil mesh."""
    return int(mesh.shape[AXIS_X]), int(mesh.shape[AXIS_Y])


def _pencil_divisible(N0, N1, px, py):
    """Whether (N0, N1) decomposes into (Px, Py) pencils: the input
    spec needs N0 % Px == 0 and N1 % Py == 0, and the outer transpose
    splits the (full) y axis Px ways. The z axis carries no constraint
    — it is zero-padded to a multiple of Py before the inner a2a."""
    return N0 % px == 0 and N1 % py == 0 and N1 % px == 0


def _fft_chunked(a, axis, norm, target, inverse=False):
    """c2c FFT along ``axis`` of a local pencil block, fori_loop-chunked
    over the other leading axis when the block exceeds the lowmem chunk
    target — the slab drivers' chunking idiom applied per pencil, so no
    single FFT op ever spans a multi-GB buffer inside the shard_map."""
    fn = jnp.fft.ifft if inverse else jnp.fft.fft
    ch = 1 if axis == 0 else 0
    n = a.shape[ch]
    r = _chunk_rows(n, max(a.size * a.dtype.itemsize // max(n, 1), 1),
                    max(target // 4, 1))
    if r >= n:
        return fn(a, axis=axis, norm=norm)
    counter('fft.trace.chunks').add(n // r)
    out = jnp.zeros(a.shape, a.dtype)
    sizes = list(a.shape)
    sizes[ch] = r

    def body(k, out):
        start = [0] * a.ndim
        start[ch] = k * r
        sl = jax.lax.dynamic_slice(a, tuple(start), tuple(sizes))
        return jax.lax.dynamic_update_slice(
            out, fn(sl, axis=axis, norm=norm), tuple(start))

    return jax.lax.fori_loop(0, n // r, body, out)


@_lru_cache(maxsize=32)
def _pencil_programs(mesh, shape, dtype_str, norm, kind, target,
                     n_out=None, a2a='none', check=False, bits1=0,
                     bits2=0):
    """The two stage programs of one pencil transform, cached per
    (mesh, shape, dtype, norm, kind, a2a wire format, integrity
    posture).  ``check`` threads the tier-0 a2a guard folds through
    both stages (each then returns ``(out, stats)``); ``bits1``/
    ``bits2`` are transient corruption injections for the chaos
    matrix (cache-keyed, so the clean program is never perturbed).

    ``kind`` is 'r2c', 'c2r', 'c2c' or 'ic2c'. Returns
    (stage1, stage2, jit1, jit2, pad): ``stage1``/``stage2`` are the
    raw shard_map callables (composable under an outer trace), and
    ``jit1``/``jit2`` their jitted forms for the eager path — ``jit2``
    donates its input so the stage-1 intermediate is aliased into the
    output and the peak stays at ~2 buffers per pencil (the lowmem
    donated-buffer idiom; nbkl's NBK5xx model prices this in
    ``pmesh.memory_plan(fft_decomp='pencil')``).
    """
    px, py = _pencil_shape(mesh)
    fwd = kind in ('r2c', 'c2c')
    inv = not fwd
    if fwd:
        N0, N1, N2 = shape
    else:
        N1, N0, NZ = shape  # transposed complex layout in
    if kind == 'r2c':
        Nz = N2 // 2 + 1  # Hermitian-compressed z length
    elif kind == 'c2r':
        Nz = NZ
    elif kind == 'c2c':
        Nz = N2
    else:  # ic2c
        Nz = NZ
    pad = -Nz % py
    Nzp = Nz + pad
    if kind == 'r2c':
        cdt = jnp.complex64 if jnp.dtype(dtype_str).itemsize <= 4 \
            else jnp.complex128
    else:
        cdt = jnp.result_type(jnp.dtype(dtype_str), jnp.complex64)

    axes = (AXIS_X, AXIS_Y)
    if fwd:
        def stage1(xl):
            # z-pencils (N0/Px, N1/Py, N2|Nz): transform z while it is
            # whole, pad to %Py, then the INNER transpose (z <-> y
            # within a 'y' group) and the y-axis transform
            if kind == 'r2c':
                y = jnp.fft.rfft(xl, axis=2, norm=norm).astype(cdt)
            else:
                y = _fft_chunked(xl.astype(cdt), 2, norm, target)
            if pad:
                y = jnp.pad(y, ((0, 0), (0, 0), (0, pad)))
            y, st = _a2a_site(y, AXIS_Y, 2, 1, py, a2a, axes, check,
                              bits1)
            out = _fft_chunked(y, 1, norm, target)
            return (out, st) if check else out

        def stage2(yl):
            # y-pencils (N0/Px, N1, Nzp/Py): the OUTER transpose
            # (y <-> x across 'x' groups), the x-axis transform, and
            # the transposed (ky-leading) output layout
            y, st = _a2a_site(yl, AXIS_X, 1, 0, px, a2a, axes, check,
                              bits2)
            y = _fft_chunked(y, 0, norm, target)
            out = jnp.transpose(y, (1, 0, 2))
            return (out, st) if check else out

        in1, out1 = P(AXIS_X, AXIS_Y, None), P(AXIS_X, None, AXIS_Y)
        in2, out2 = out1, P(AXIS_X, None, AXIS_Y)
    else:
        def stage1(yl):
            # transposed x-pencils (N1/Px, N0, Nzp/Py): undo the x-axis
            # transform, then the OUTER transpose back
            z = jnp.transpose(yl, (1, 0, 2))
            z = _fft_chunked(z, 0, norm, target, inverse=True)
            z, st = _a2a_site(z, AXIS_X, 0, 1, px, a2a, axes, check,
                              bits1)
            out = _fft_chunked(z, 1, norm, target, inverse=True)
            return (out, st) if check else out

        def stage2(zl):
            # y-pencils (N0/Px, N1, Nzp/Py): the INNER transpose back
            # (z whole again), drop the pad locally, undo the z-axis
            # transform
            z, st = _a2a_site(zl, AXIS_Y, 1, 2, py, a2a, axes, check,
                              bits2)
            if pad:
                z = z[:, :, :Nz]
            if kind == 'c2r':
                out = jnp.fft.irfft(z, n=int(n_out), axis=2,
                                    norm=norm)
            else:
                out = _fft_chunked(z, 2, norm, target, inverse=True)
            return (out, st) if check else out

        in1, out1 = P(AXIS_X, None, AXIS_Y), P(AXIS_X, None, AXIS_Y)
        in2, out2 = out1, P(AXIS_X, AXIS_Y, None)

    o1 = (out1, P(None)) if check else out1
    o2 = (out2, P(None)) if check else out2
    s1 = jax.shard_map(stage1, mesh=mesh, in_specs=in1, out_specs=o1)
    s2 = jax.shard_map(stage2, mesh=mesh, in_specs=in2, out_specs=o2)
    label = 'fft.pencil.%s' % kind
    j1 = instrumented_jit(s1, label=label + '.inner')
    j2 = instrumented_jit(s2, label=label + '.outer',
                          donate_argnums=(0,))
    return s1, s2, j1, j2, pad


def _pencil_run(x, mesh, norm, kind, Nz_out=None):
    """Run one pencil transform as its two stages. Eagerly each stage
    dispatches as a separate jitted program wrapped in a span —
    ``fft.a2a.inner`` / ``fft.a2a.outer`` — so diagnostics/analyze.py
    attributes ICI (inner, within a 'y' group) and DCN (outer, across
    'x' groups) transpose time separately; stage 2 donates the stage-1
    intermediate. Under an outer trace the raw shard_map stages compose
    into the caller's graph (donation and spans are the trace's
    concern there)."""
    px, py = _pencil_shape(mesh)
    target = _fft_chunk_bytes() or 2 ** 31
    eager = is_eager(x)
    # integrity posture + chaos injection resolve at dispatch: each
    # stage's a2a is one 'a2a.payload' injection consult, and guard
    # comparison is eager-only (a data-dependent raise cannot live
    # under trace — traced composition keeps the unchecked programs)
    bits1 = _corrupt_bits() if eager else 0
    bits2 = _corrupt_bits() if eager else 0
    chk = eager and _integrity_on()
    a2a = _a2a_mode()
    nglobal = int(x.size)
    s1, s2, j1, j2, pad = _pencil_programs(
        mesh, tuple(int(n) for n in x.shape), str(x.dtype), norm, kind,
        int(target), None if Nz_out is None else int(Nz_out),
        a2a, chk, bits1, bits2)
    if kind in ('c2r', 'ic2c') and pad:
        # the complex input's z axis is padded back to the transform's
        # internal %Py multiple; the pad columns are zeros and are
        # dropped locally after the inner transpose
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    with scope('fft.a2a.inner', kind=kind, group=py,
               pencil=[px, py]):
        mid = (j1 if eager else s1)(x)
    del x
    if chk:
        mid, st1 = mid
        _a2a_verify('a2a.pencil.%s.stage1' % kind, st1, a2a, nglobal)
    with scope('fft.a2a.outer', kind=kind, group=px,
               pencil=[px, py]):
        out = (j2 if eager else s2)(mid)
    if chk:
        out, st2 = out
        _a2a_verify('a2a.pencil.%s.stage2' % kind, st2, a2a, nglobal)
    if kind in ('r2c', 'c2c') and pad:
        # the forward output carries zero pad columns on the z axis
        # (they lived on the last 'y' rank); slice back to the
        # contract's Nc | N2 length
        out = out[:, :, :out.shape[2] - pad]
    return out


def _pencil_fallback_mesh(mesh, N0, N1):
    """For shapes that do not factor into (Px, Py) pencils: the slab
    view of the same devices when the slab constraint holds, else None
    (single-device semantics — GSPMD gathers)."""
    n = mesh_size(mesh)
    if N0 % n == 0 and N1 % n == 0:
        return Mesh(mesh.devices.reshape(-1), (AXIS,))
    return None


def _pencil_dispatch(x, mesh, kind, run, fallback):
    """Dispatch a transform on a 2-D mesh: the pencil path when the
    shape factors, else the slab path over the flattened device order,
    else single-device semantics. ``fallback(mesh_or_none)`` reruns
    the caller's impl; ragged shapes therefore stay exact rather than
    zero-padded (padding would change the transform)."""
    px, py = _pencil_shape(mesh)
    if kind in ('r2c', 'c2c'):
        N0, N1 = int(x.shape[0]), int(x.shape[1])
    else:
        N1, N0 = int(x.shape[0]), int(x.shape[1])
    if _pencil_divisible(N0, N1, px, py):
        return run()
    counter('fft.pencil.fallback').add(1)
    return fallback(_pencil_fallback_mesh(mesh, N0, N1))


@_lru_cache(maxsize=32)
def _slab_programs(mesh, norm, kind, n_out=None, a2a='none', check=False,
                   bits=0):
    """One slab transform as one program, cached per (mesh, norm, kind,
    a2a wire format, integrity posture); shapes and dtypes key the
    jit's own cache.  ``check`` threads the tier-0 a2a guard folds
    through (the program then returns ``(out, stats)``); ``bits`` is a
    transient corruption injection for the chaos matrix (cache-keyed,
    so the clean program is never perturbed), as in
    :func:`_pencil_programs`.

    ``kind`` is 'r2c', 'c2r', 'c2c' or 'ic2c'.  Returns ``(raw, jit)``:
    the raw shard_map callable (composable under an outer trace) and
    its jitted form for the eager path, where an eager ``shard_map``
    would run the body one primitive a program and trace, lower and
    look each up again on every call."""
    nproc = mesh_size(mesh)
    if kind in ('r2c', 'c2c'):
        def passes(v):
            if kind == 'r2c':
                y = jnp.fft.rfft(_fft_operand(v), axis=2, norm=norm)
            else:
                y = jnp.fft.fft(v, axis=2, norm=norm)
            y = jnp.fft.fft(y, axis=1, norm=norm)
            # (N0/P, N1, Nc) -> (N0, N1/P, Nc)
            y, st = _a2a_site(y, AXIS, 1, 0, nproc, a2a, (AXIS,),
                              check, bits)
            y = jnp.fft.fft(y, axis=0, norm=norm)
            return jnp.transpose(y, (1, 0, 2)), st
    else:
        def passes(v):
            # (N1/P, N0, Nc) -> (N0, N1/P, Nc)
            z = jnp.transpose(v, (1, 0, 2))
            z = jnp.fft.ifft(z, axis=0, norm=norm)
            # (N0, N1/P, Nc) -> (N0/P, N1, Nc)
            z, st = _a2a_site(z, AXIS, 0, 1, nproc, a2a, (AXIS,),
                              check, bits)
            z = jnp.fft.ifft(z, axis=1, norm=norm)
            if kind == 'c2r':
                z = jnp.fft.irfft(z, n=n_out, axis=2, norm=norm)
            else:
                z = jnp.fft.ifft(z, axis=2, norm=norm)
            return z, st

    def local(v):
        # the layer's name on the passes' own op names: an instruction
        # with none takes its users', and the passes before the
        # all_to_all would read as ``nbk.fft.a2a.<axis>``
        with scope('fft.%s' % ('c2c' if kind == 'ic2c' else kind)):
            out, st = passes(v)
        return (out, st) if check else out

    local.__name__ = 'slab_fft_%s' % kind     # the program's name
    raw = jax.shard_map(
        local, mesh=mesh, in_specs=P(AXIS, None, None),
        out_specs=(P(AXIS, None, None), P(None)) if check
        else P(AXIS, None, None))
    return raw, instrumented_jit(raw, label='fft.slab.%s' % kind)


def _slab_run(x, mesh, norm, kind, n_out=None):
    """Run one slab transform: eagerly the cached jitted program of
    :func:`_slab_programs`, under an outer trace its raw shard_map.
    Integrity posture and chaos injection resolve here, at dispatch,
    and are eager-only (a data-dependent raise cannot live under
    trace), as in :func:`_pencil_run`."""
    eager = is_eager(x)
    a2a = _a2a_mode()
    bits = _corrupt_bits() if eager else 0
    chk = eager and _integrity_on()
    raw, jitted = _slab_programs(
        mesh, norm, kind, None if n_out is None else int(n_out), a2a,
        chk, bits)
    res = (jitted if eager else raw)(x)
    if chk:
        res, st = res
        _a2a_verify('a2a.slab.%s' % kind, st, a2a, int(x.size))
    return res


def dist_rfftn(x, mesh=None, norm=None):
    """3-D rFFT of a slab-sharded real field; returns the transposed-layout
    complex field (see module docstring).

    Parameters
    ----------
    x : jax.Array, global shape (N0, N1, N2), real
    mesh : jax.sharding.Mesh or None
        1-D device mesh; None or size-1 → single-device path.
    norm : None or 'ortho' — forwarded to the FFT stages.

    Returns
    -------
    jax.Array, global shape (N1, N0, N2//2 + 1), complex, sharded on axis 0.

    Notes
    -----
    Single-device fields past ``fft_chunk_bytes`` dispatch to the
    eager lowmem driver; via this entry point the peak is ~3
    full-mesh buffers (the caller's reference to ``x`` stays live
    through the transform).  For the driver's ~2-buffer ownership
    contract call :func:`rfftn_single_lowmem` directly.
    """
    eager = not isinstance(x, jax.core.Tracer)
    chk = eager and _integrity_on()
    shape = tuple(int(s) for s in x.shape)
    if chk:
        # the input power, folded BEFORE the transform consumes the
        # field (the lowmem driver may free it); compared against the
        # Hermitian-weighted output power after — the Parseval bracket
        # (docs/INTEGRITY.md), which also trips on any NaN/Inf that
        # poisons a mesh-sized intermediate
        sx = float(jnp.sum(jnp.square(
            jnp.real(jnp.asarray(x)).astype(jnp.float32))))
    with scope('fft.r2c', nproc=mesh_size(mesh),
               shape=list(shape)) as sc:
        out = _dist_rfftn_impl(x, mesh, norm)
        # a statement, not a pass-through: the lint's peak model books
        # a call's result as one more mesh-sized buffer
        sc.done(out)
    if chk:
        _parseval_verify('fft.parseval.r2c', shape, sx, out, norm)
    return out


def _dist_rfftn_impl(x, mesh, norm):
    nproc = mesh_size(mesh)
    if is_pencil(mesh) and nproc > 1:
        return _pencil_dispatch(
            x, mesh, 'r2c',
            lambda: _pencil_run(x, mesh, norm, 'r2c'),
            lambda m: _dist_rfftn_impl(x, m, norm))
    if nproc == 1:
        N0, N1, N2 = x.shape
        target = _fft_chunk_bytes()
        out_bytes = N0 * N1 * (N2 // 2 + 1) * (
            8 if x.dtype.itemsize <= 4 else 16)
        if target and out_bytes > target:
            if not isinstance(x, jax.core.Tracer):
                # eager call on a concrete field (the production
                # compute() pipeline composes eagerly): the Python-
                # driven lowmem driver peaks ~1 full-mesh buffer lower
                # than the in-jit chunked program and avoids eager
                # multi-GB ops the backend may not support
                box = [x]
                x = None  # this frame's ref must not pin the input
                return rfftn_single_lowmem(box, norm=norm,
                                           target=target)
            return _rfftn_single_chunked(x, norm, target)
        y = jnp.fft.rfftn(_fft_operand(x), norm=norm)
        return jnp.transpose(y, (1, 0, 2))

    N0, N1, N2 = x.shape
    if N0 % nproc or N1 % nproc:
        raise ValueError("Nmesh[0] and Nmesh[1] must be divisible by the "
                         "device count %d, got %s" % (nproc, (N0, N1, N2)))
    return _slab_run(x, mesh, norm, 'r2c')


def dist_irfftn(y, Nmesh2, mesh=None, norm=None):
    """Inverse of :func:`dist_rfftn`.

    Parameters
    ----------
    y : jax.Array, global shape (N1, N0, Nc), complex, transposed layout
    Nmesh2 : int — the last real-space dimension N2 (since Nc = N2//2+1
        is ambiguous).

    Returns
    -------
    jax.Array, global shape (N0, N1, N2), real, sharded on axis 0.
    """
    with scope('fft.c2r', nproc=mesh_size(mesh),
               shape=[int(s) for s in y.shape]) as sc:
        out = _dist_irfftn_impl(y, Nmesh2, mesh, norm)
        sc.done(out)
    return out


def _dist_irfftn_impl(y, Nmesh2, mesh, norm):
    nproc = mesh_size(mesh)
    if is_pencil(mesh) and nproc > 1:
        return _pencil_dispatch(
            y, mesh, 'c2r',
            lambda: _pencil_run(y, mesh, norm, 'c2r', Nz_out=Nmesh2),
            lambda m: _dist_irfftn_impl(y, Nmesh2, m, norm))
    if nproc == 1:
        target = _fft_chunk_bytes()
        if target and y.nbytes > target:
            if not isinstance(y, jax.core.Tracer):
                box = [y]
                y = None  # this frame's ref must not pin the input
                return irfftn_single_lowmem(box, Nmesh2, norm=norm,
                                            target=target)
            return _irfftn_single_chunked(y, Nmesh2, norm, target)
        yt = jnp.transpose(y, (1, 0, 2))
        return jnp.fft.irfftn(yt, s=(yt.shape[0], yt.shape[1], Nmesh2), norm=norm)

    return _slab_run(y, mesh, norm, 'c2r', n_out=Nmesh2)


def _fftn_c2c_single_chunked(x, inverse, norm, target):
    """Slab-chunked per-axis c2c transform (same rationale as
    :func:`_rfftn_single_chunked`: no FFT op ever spans a multi-GB
    buffer).  Forward maps (N0, N1, N2) -> transposed (N1, N0, N2);
    inverse is the exact reverse."""
    fft = jnp.fft.ifft if inverse else jnp.fft.fft
    op_target = max(target // 4, 1)
    csz = x.dtype.itemsize
    if inverse:
        N1, N0, N2 = x.shape
    else:
        N0, N1, N2 = x.shape

    if not inverse:
        # pass A: fft z + fft y over x-slabs; pass B: fft x over
        # y-slabs, written transposed
        r0 = _chunk_rows(N0, N1 * N2 * csz, op_target)
        y = jnp.zeros((N0, N1, N2), x.dtype)

        def body_a(i, y):
            sl = jax.lax.dynamic_slice(x, (i * r0, 0, 0), (r0, N1, N2))
            s = fft(fft(sl, axis=2, norm=norm), axis=1, norm=norm)
            return jax.lax.dynamic_update_slice(y, s, (i * r0, 0, 0))

        y = jax.lax.fori_loop(0, N0 // r0, body_a, y)
        r1 = _chunk_rows(N1, N0 * N2 * csz, op_target)
        out = jnp.zeros((N1, N0, N2), x.dtype)

        def body_b(j, out):
            sl = jax.lax.dynamic_slice(y, (0, j * r1, 0), (N0, r1, N2))
            s = jnp.transpose(fft(sl, axis=0, norm=norm), (1, 0, 2))
            return jax.lax.dynamic_update_slice(out, s, (j * r1, 0, 0))

        return jax.lax.fori_loop(0, N1 // r1, body_b, out)

    # inverse: undo fft x (axis 1 of the transposed layout) over
    # ky-slabs, then fft y + fft z over x-slabs
    r1 = _chunk_rows(N1, N0 * N2 * csz, op_target)
    z = jnp.zeros((N0, N1, N2), x.dtype)

    def body_a(j, z):
        sl = jax.lax.dynamic_slice(x, (j * r1, 0, 0), (r1, N0, N2))
        s = jnp.transpose(fft(sl, axis=1, norm=norm), (1, 0, 2))
        return jax.lax.dynamic_update_slice(z, s, (0, j * r1, 0))

    z = jax.lax.fori_loop(0, N1 // r1, body_a, z)
    r0 = _chunk_rows(N0, N1 * N2 * csz, op_target)
    out = jnp.zeros((N0, N1, N2), x.dtype)

    def body_b(i, out):
        sl = jax.lax.dynamic_slice(z, (i * r0, 0, 0), (r0, N1, N2))
        s = fft(fft(sl, axis=1, norm=norm), axis=2, norm=norm)
        return jax.lax.dynamic_update_slice(out, s, (i * r0, 0, 0))

    return jax.lax.fori_loop(0, N0 // r0, body_b, out)


def dist_fftn_c2c(x, mesh=None, inverse=False, norm=None):
    """Full complex-to-complex 3-D FFT, transposed layout in/out.

    Forward: input (N0, N1, N2) untransposed -> output (N1, N0, N2)
    transposed. Inverse: the reverse. Used by the white-noise generator
    and ConvolvedFFTPower's Ylm products where a c2c view is simpler.
    """
    with scope('fft.c2c', nproc=mesh_size(mesh), inverse=bool(inverse),
               shape=[int(s) for s in x.shape]) as sc:
        out = _dist_fftn_c2c_impl(x, mesh, inverse, norm)
        sc.done(out)
    return out


def _dist_fftn_c2c_impl(x, mesh, inverse, norm):
    nproc = mesh_size(mesh)
    if is_pencil(mesh) and nproc > 1:
        kind = 'ic2c' if inverse else 'c2c'
        return _pencil_dispatch(
            x, mesh, kind,
            lambda: _pencil_run(x, mesh, norm, kind),
            lambda m: _dist_fftn_c2c_impl(x, m, inverse, norm))
    if nproc == 1:
        target = _fft_chunk_bytes()
        if target and x.nbytes > target:
            if not isinstance(x, jax.core.Tracer):
                # eager call on a concrete field (convpower's Ylm loop
                # composes eagerly): the Python-driven lowmem driver,
                # as for r2c/c2r above — eager multi-GB fori_loop
                # programs are exactly what the backend may refuse
                box = [x]
                x = None  # this frame's ref must not pin the input
                return fftn_c2c_single_lowmem(box, inverse=inverse,
                                              norm=norm, target=target)
            return _fftn_c2c_single_chunked(x, inverse, norm, target)
        if inverse:
            y = jnp.transpose(x, (1, 0, 2))
            return jnp.fft.ifftn(y, norm=norm)
        return jnp.transpose(jnp.fft.fftn(x, norm=norm), (1, 0, 2))

    return _slab_run(x, mesh, norm, 'ic2c' if inverse else 'c2c')


def _parse_pencil(v):
    """Parse an fft_pencil option value: 'PXxPY', (px, py) or None."""
    if v in (None, ''):
        return None
    if isinstance(v, str):
        px, _, py = v.lower().partition('x')
        return int(px), int(py)
    px, py = v
    return int(px), int(py)


def resolve_decomp(nproc, decomp=None, pencil=None):
    """The fft_decomp knob as ('slab'|'pencil', (Px, Py)).

    Explicit arguments win over ``set_options(fft_decomp=...)`` /
    ``set_options(fft_pencil=...)``. Returns ('slab', None) for
    nproc <= 1.
    """
    if nproc <= 1:
        return 'slab', None
    from .. import _global_options
    opts = _global_options.copy()
    decomp = decomp or opts.get('fft_decomp', 'slab')
    pxpy = _parse_pencil(
        pencil if pencil is not None else opts.get('fft_pencil'))
    if pxpy is None:
        pxpy = default_pencil_factor(nproc)
    if pxpy[0] * pxpy[1] != nproc:
        raise ValueError(
            "fft_pencil %dx%d does not cover %d devices" %
            (pxpy[0], pxpy[1], nproc))
    if decomp not in ('slab', 'pencil'):
        raise ValueError("fft_decomp must be 'slab' or 'pencil', "
                         "got %r" % (decomp,))
    return decomp, pxpy


class dist_fft_plan(object):
    """A small plan object bundling mesh + shape, so call sites read like
    the reference's ``field.r2c()`` / ``field.c2r()``.

    The slab-vs-pencil decomposition is read *at dispatch*, per
    call: ``set_options(fft_decomp='pencil')`` reroutes the next
    transform through the 2-D pencil path with no plan rebuild. An
    explicit 2-D mesh handed to the plan wins outright; a 1-D mesh is
    viewed as its (Px, Py) pencil factorization on demand (same
    devices, row-major order, so slab- and pencil-sharded fields
    interconvert without data movement).
    """

    def __init__(self, Nmesh, mesh=None, decomp=None, pencil=None):
        self.Nmesh = tuple(int(n) for n in Nmesh)
        self.mesh = mesh
        self._decomp = decomp    # explicit override ('slab'|'pencil')
        self._pencil = pencil    # explicit (Px, Py) or 'PXxPY' override
        self._pencil_cache = {}  # (Px, Py) -> 2-D mesh view

    def _dispatch_mesh(self):
        """The mesh the next transform runs on, by the fft_decomp
        knob (see :func:`resolve_decomp`)."""
        mesh = self.mesh
        if mesh is None or is_pencil(mesh):
            return mesh
        nproc = mesh_size(mesh)
        if nproc == 1:
            return mesh
        decomp, pxpy = resolve_decomp(
            nproc, decomp=self._decomp, pencil=self._pencil)
        if decomp != 'pencil':
            return mesh
        if pxpy not in self._pencil_cache:
            self._pencil_cache[pxpy] = pencil_mesh(
                *pxpy, devices=list(mesh.devices.reshape(-1)))
        return self._pencil_cache[pxpy]

    def r2c(self, x, norm=None):
        return dist_rfftn(x, self._dispatch_mesh(), norm=norm)

    def c2r(self, y, norm=None):
        return dist_irfftn(y, self.Nmesh[2], self._dispatch_mesh(),
                           norm=norm)

    def c2c(self, x, inverse=False, norm=None):
        return dist_fftn_c2c(x, self._dispatch_mesh(),
                             inverse=inverse, norm=norm)
