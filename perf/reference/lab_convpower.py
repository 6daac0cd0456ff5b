"""ConvolvedFFTPower in plain numpy, f8: the survey-geometry multipole
estimator as upstream nbodykit implements it (Hand et al. 2017, the
FFT form of the Yamamoto estimator after Bianchi et al. 2015 and
Scoccimarro 2015; normalisation and shot noise from Beutler et al.
2014, eqs. 13-15).  It shares no code with ``nbodykit_tpu``: what the
package computes with ``FKPCatalog(data, randoms).to_mesh(Nmesh,
resampler='tsc')`` and ``ConvolvedFFTPower(mesh, poles, dk=...)`` is
written out here from the definitions, so that a wrong sign,
normalisation or ``m`` in the package's ``Y_lm`` sum shows as a
number.

    F(x)     = [sum_data w_c w_fkp W(x - x_i)
                - alpha sum_randoms w_c w_fkp W(x - x_j)] / V_cell
    alpha    = sum_data w_c / sum_randoms w_c
    A_ell(k) = 4 pi V sum_m Y_lm(k^) FFT[F(x) Y_lm(x^)](k) / C(k)
    A_0(k)   = V FFT[F](k) / C(k)
    P_ell(k) = A_0(k) conj(A_ell(k)) / A,  A = alpha sum_randoms nbar w_c w_fkp^2
    S        = [sum_data (w_c w_fkp)^2 + alpha^2 sum_randoms (w_c w_fkp)^2] / A

with ``W`` the triangular-shaped-cloud window, ``FFT`` divided by
Nmesh^3, ``Y_lm`` the real spherical harmonics written as polynomials
in the unit vector, and ``P_ell`` averaged in shells ``arange(kmin,
kmax, dk)`` over the half lattice of the real transform with its
Hermitian partners counted.

Where this follows upstream's code and not the papers:

- the box is the randoms' extent padded by 2% and rounded up to whole
  numbers, side by side, centred on the middle of that extent;
  particles are re-centred on it and wrapped;
- the mesh point ``i`` is taken to sit at ``(i + 1/2) H - L/2 +
  centre`` when the line of sight ``x^`` is formed, half a cell from
  where the deposit puts it (upstream's ``offset = BoxCenter + 0.5 *
  BoxSize / Nmesh``);
- ``C(k)`` is the window with its first-order aliasing (Jing 2005,
  eq. 20, which upstream's ``CompensateTSCShotnoise`` and this
  package's ``compensation_transfer('tsc', False)`` divide out), not
  the bare sinc^3 of eq. 18;
- the DC mode is kept;
- on the half lattice an even multipole keeps twice the real part of
  a mode with a distinct partner and the whole value of one on the
  planes k_z = 0 and k_z = Nyquist, so the imaginary part of a shell
  is that of those planes alone;
- the factor ``2 ell + 1`` is not in ``A_ell``: the addition theorem
  ``sum_m Y_lm(a) Y_lm(b) = (2 ell + 1) / (4 pi) L_ell(a . b)`` brings
  it.

It also runs at the survey cell's own size (1.1e7 particles, 512^3)
after every timed window, so what is mesh-sized goes through
:func:`in_slabs`: a few planes at a time (temporaries that stay in the
cache) on the host's cores (numpy's loops release the GIL), and the
transform is ``scipy.fft.rfftn`` with ``workers``, pocketfft as
``numpy.fft`` is.  Written whole-mesh and single-threaded it took
540 s there on 8 cores; so, 42-46 s on the 13 of the cell's host.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy import fft

CORES = os.cpu_count() or 1


def in_slabs(fn, n, rows=4):
    """``fn(planes)`` for every slab of ``rows`` planes of an axis of
    length ``n``, on the host's cores; the results in order."""
    slabs = [slice(i, min(i + rows, n)) for i in range(0, n, rows)]
    with ThreadPoolExecutor(CORES) as pool:
        return list(pool.map(fn, slabs))


def _c(num, den=1.0):
    return np.sqrt(num / (den * np.pi))


#: real spherical harmonics on unit vectors, ell -> one function of
#: (x, y, z) per m = -ell .. ell
REAL_YLM = {
    0: [lambda x, y, z: 0.5 * _c(1) + 0 * z],
    2: [lambda x, y, z: 0.5 * _c(15) * x * y,
        lambda x, y, z: 0.5 * _c(15) * y * z,
        lambda x, y, z: 0.25 * _c(5) * (3 * z * z - 1),
        lambda x, y, z: 0.5 * _c(15) * x * z,
        lambda x, y, z: 0.25 * _c(15) * (x * x - y * y)],
    4: [lambda x, y, z: 0.75 * _c(35) * x * y * (x * x - y * y),
        lambda x, y, z: 0.75 * _c(35, 2) * y * (3 * x * x - y * y) * z,
        lambda x, y, z: 0.75 * _c(5) * x * y * (7 * z * z - 1),
        lambda x, y, z: 0.75 * _c(5, 2) * y * z * (7 * z * z - 3),
        lambda x, y, z: 3.0 / 16 * _c(1) * (35 * z ** 4 - 30 * z * z + 3),
        lambda x, y, z: 0.75 * _c(5, 2) * x * z * (7 * z * z - 3),
        lambda x, y, z: 0.375 * _c(5) * (x * x - y * y) * (7 * z * z - 1),
        lambda x, y, z: 0.75 * _c(35, 2) * x * (x * x - 3 * y * y) * z,
        lambda x, y, z: 3.0 / 16 * _c(35) * (
            x * x * (x * x - 3 * y * y) - y * y * (3 * x * x - y * y))],
}


def survey_box(randoms_pos, pad=0.02):
    """BoxSize and BoxCenter from the randoms' extent."""
    lo = np.min(randoms_pos, axis=0)
    hi = np.max(randoms_pos, axis=0)
    return np.ceil(np.abs(hi - lo) * (1.0 + pad)), 0.5 * (lo + hi)


def tsc_deposit(cells, weight, N):
    """Triangular-shaped-cloud deposit of ``weight`` at positions
    ``cells`` (in units of the cell, mesh points at the integers) on a
    periodic ``N``^3 mesh."""
    near = np.floor(cells + 0.5).astype('i8')
    order = np.argsort(near[:, 0] % N, kind='stable')
    d, weight = (cells - near)[order].T, weight[order]
    near = near[order].T % N
    # the weights of the mesh points near - 1, near, near + 1
    w1 = [0.5 * (0.5 - d) ** 2, 0.75 - d * d, 0.5 * (0.5 + d) ** 2]

    def deposit(planes):
        """The particles whose nearest plane is one of ``planes``,
        onto those and the one before and after."""
        lo, hi = np.searchsorted(near[0], [planes.start, planes.stop])
        rows = planes.stop - planes.start + 2
        local = np.zeros(rows * N * N)
        for a in range(3):
            ia = (near[0, lo:hi] - planes.start + a) * N
            wa = weight[lo:hi] * w1[a][0, lo:hi]
            for b in range(3):
                iab = (ia + (near[1, lo:hi] + b - 1) % N) * N
                wab = wa * w1[b][1, lo:hi]
                for c in range(3):
                    local += np.bincount(
                        iab + (near[2, lo:hi] + c - 1) % N,
                        weights=wab * w1[c][2, lo:hi],
                        minlength=len(local))
        return np.arange(planes.start - 1, planes.stop + 1) % N, local

    field = np.zeros((N, N, N))
    for rows, local in in_slabs(deposit, N):
        for row, plane in zip(rows, local.reshape(-1, N, N)):
            field[row] += plane
    return field


def fkp_field(data, randoms, BoxSize, BoxCenter, N, alpha):
    """``F(x)``: each species a dict with ``pos``, ``comp``, ``fkp``."""
    H = BoxSize / N
    cells = [(species['pos'] - BoxCenter + 0.5 * BoxSize) / H
             for species in (data, randoms)]
    weight = [sign * species['comp'] * species['fkp'] / np.prod(H)
              for species, sign in ((data, 1.0), (randoms, -alpha))]
    return tsc_deposit(np.concatenate(cells), np.concatenate(weight), N)


def _unit(vectors):
    norm = np.sqrt(sum(v * v for v in vectors))
    # (the zero vector stays zero)
    norm = np.where(norm == 0, np.inf, norm)
    return [v / norm for v in vectors]


def reference_convpower(data, randoms, Nmesh, poles, dk, kmin=0.0,
                        quantize=None):
    """The multipoles and attrs of ``ConvolvedFFTPower(FKPCatalog(
    data, randoms).to_mesh(Nmesh, resampler='tsc'), poles, dk=dk,
    kmin=kmin)``.

    ``data`` and ``randoms``: dicts of ``pos`` (n, 3), ``comp``,
    ``fkp`` and ``nbar`` (n,).  ``quantize``, where given, is applied
    to every mesh-sized field as it is stored (``F(x)``, each
    ``F(x) Y_lm(x^)``, each transform's real and imaginary part): how
    the estimator reads with its fields kept in a coarser number
    format.

    Returns ``k``, ``modes``, ``power_<ell>`` (complex) and ``alpha``,
    ``data.norm``, ``randoms.norm``, ``shotnoise``, ``BoxSize``,
    ``BoxCenter``."""
    N = int(Nmesh)
    L, centre = survey_box(randoms['pos'])
    V = float(np.prod(L))
    alpha = data['comp'].sum() / randoms['comp'].sum()
    A_data = np.sum(data['nbar'] * data['comp'] * data['fkp'] ** 2)
    A_ran = alpha * np.sum(randoms['nbar'] * randoms['comp']
                           * randoms['fkp'] ** 2)
    shot = (np.sum((data['comp'] * data['fkp']) ** 2) + alpha ** 2
            * np.sum((randoms['comp'] * randoms['fkp']) ** 2)) / A_ran

    stored = quantize or (lambda field: field)

    def real_field(make, out):
        """The mesh-sized field of which ``make(planes)`` is a slab,
        as stored, written to ``out``."""
        def slab(planes):
            out[planes] = stored(make(planes))
        in_slabs(slab, N)
        return out

    F = fkp_field(data, randoms, L, centre, N, alpha)
    F = real_field(lambda planes: F[planes], F)
    weighted = np.empty_like(F)

    H = L / N
    i = np.fft.fftfreq(N, 1.0 / N)
    iz = np.arange(N // 2 + 1)
    index = [i[:, None, None], i[None, :, None], iz[None, None, :]]
    kvec = [2 * np.pi / L[a] * index[a] for a in range(3)]
    grid = np.arange(N)
    xvec = [((grid + 0.5) * H[a] - 0.5 * L[a] + centre[a]).reshape(
        [-1 if b == a else 1 for b in range(3)]) for a in range(3)]
    # the window is a product of one factor an axis
    wvec = []
    for a in range(3):
        s2 = np.sin(np.pi * index[a] / N) ** 2
        wvec.append(np.sqrt(1 - s2 + 2.0 / 15 * s2 ** 2))

    def of(vectors, planes):
        """Three axis vectors, the first cut to ``planes``: they
        broadcast to that slab of the mesh."""
        return [vectors[0][planes], vectors[1], vectors[2]]

    def transform(field):
        out = fft.rfftn(field, workers=CORES, norm='forward')
        if quantize:
            def slab(planes):
                out[planes] = stored(out[planes].real) \
                    + 1j * stored(out[planes].imag)
            in_slabs(slab, N)
        return out

    def compensated(A, factor):
        def slab(planes):
            w = of(wvec, planes)
            A[planes] = factor * A[planes] / (w[0] * w[1] * w[2])
        in_slabs(slab, N)
        return A

    def a_ell(ell):
        A = np.zeros_like(A0)
        for Y in REAL_YLM[ell]:
            c = transform(real_field(lambda planes: F[planes] * Y(
                *_unit(of(xvec, planes))), weighted))

            def slab(planes):
                A[planes] += Y(*_unit(of(kvec, planes))) * c[planes]
            in_slabs(slab, N)
        return compensated(A, 4 * np.pi * V)

    A0 = compensated(transform(F), V)
    kedges = np.arange(kmin, np.pi * N / L.max() + dk / 2, dk)
    nk = len(kedges) - 1
    twice = np.where((iz == 0) | (2 * iz == N), 1.0, 2.0)

    def shells(columns):
        """The sums over the dk shells of each array that
        ``columns(planes, k2)`` gives for a slab of the half lattice."""
        def slab(planes):
            k2 = sum(k * k for k in of(kvec, planes))
            shell = np.digitize(k2, kedges ** 2)
            inside = (shell >= 1) & (shell <= nk)
            return [np.bincount(
                shell[inside] - 1, minlength=nk,
                weights=np.broadcast_to(v, k2.shape)[inside])
                for v in columns(planes, k2)]
        return np.sum(in_slabs(slab, N), axis=0)

    modes, ksum = shells(lambda planes, k2: [twice, np.sqrt(k2) * twice])
    out = {'modes': modes, 'alpha': alpha, 'data.norm': A_data,
           'randoms.norm': A_ran, 'shotnoise': shot, 'BoxSize': L,
           'BoxCenter': centre}
    with np.errstate(invalid='ignore', divide='ignore'):
        out['k'] = ksum / modes
        for ell in poles:
            A = A0 if ell == 0 else a_ell(ell)

            def power(planes, k2):
                # an even multipole's imaginary part cancels between
                # a mode and its partner; the planes that hold both
                # keep it
                P = A0[planes] * np.conj(A[planes]) / A_ran
                return [P.real * twice, P.imag * (twice == 1.0)]
            re, im = shells(power)
            out['power_%d' % ell] = (re + 1j * im) / modes
    return out


def round_to_bfloat16(field):
    """``field`` rounded to the nearest bfloat16 (8 bits of mantissa),
    ties to even, returned as f8."""
    bits = np.asarray(field, 'f4').view('u4')
    # (the sum wraps only for a NaN)
    bits = (bits + (np.uint32(0x7fff) + ((bits >> np.uint32(16))
                                         & np.uint32(1)))
            ) & np.uint32(0xffff0000)
    return bits.view('f4').astype('f8')
