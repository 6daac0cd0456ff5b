"""FFTPower(mode='2d') of a uniform-weight catalog in plain numpy.

The plain reference of the ``lab_fftpower`` traffic kind: written from
the estimator's definition, sharing no code with ``nbodykit_tpu``
(copied from ``chip_smoke.py:reference_fftpower``, which ran against
the chip in PR 22, so that a later PR cannot change the yardstick by
changing that script)."""

import numpy as np


def shell_thresholds(Nmesh, BoxSize, kmin=0.0):
    """FFTPower's default k edges (from ``kmin`` in steps of dk =
    2 pi / BoxSize, up to the Nyquist frequency plus dk / 2) as
    thresholds on the integer lattice: mode i lies in shell b iff
    q[b] <= |i|^2 < q[b+1].  With kmin = 0 modes sit exactly on these
    edges (every |i|^2 that is a perfect square), so the shell is
    decided in integers, never by how a square root rounds."""
    dk = 2 * np.pi / float(BoxSize)
    kedges = np.arange(float(kmin),
                       np.pi * int(Nmesh) / float(BoxSize) + dk / 2, dk)
    return np.ceil((kedges / dk) ** 2).astype('i8')


def cic_density(pos, BoxSize, Nmesh):
    """1 + delta of unit-weight particles by cloud-in-cell deposit."""
    N, L = int(Nmesh), float(BoxSize)
    x = np.asarray(pos, 'f8') * (N / L)
    i0 = np.floor(x).astype('i8')
    f = x - i0
    field = np.zeros(N ** 3)
    for a in (0, 1):
        for b in (0, 1):
            for c in (0, 1):
                w = (np.abs(1 - a - f[:, 0]) * np.abs(1 - b - f[:, 1])
                     * np.abs(1 - c - f[:, 2]))
                lin = ((((i0[:, 0] + a) % N) * N
                        + (i0[:, 1] + b) % N) * N + (i0[:, 2] + c) % N)
                field += np.bincount(lin, weights=w, minlength=N ** 3)
    return field.reshape(N, N, N) / (len(x) / float(N ** 3))


def power_3d(field, BoxSize):
    """|delta_k|^2 V of a real field on the half lattice, the CIC
    window and its first-order aliasing divided out (Jing 2005,
    eq. 20), the DC mode cleared; with the integer axes."""
    N, L = field.shape[0], float(BoxSize)
    ck = np.fft.rfftn(field) / N ** 3
    ix = np.fft.fftfreq(N, 1.0 / N).astype('i8')
    iz = np.arange(N // 2 + 1)
    for ax, i in enumerate((ix, ix, iz)):
        w = 2 * np.pi * i / N
        shape = [1, 1, 1]
        shape[ax] = -1
        ck = ck / np.sqrt(1 - 2.0 / 3 * np.sin(0.5 * w) ** 2
                          ).reshape(shape)
    p3 = np.abs(ck) ** 2 * L ** 3
    p3[0, 0, 0] = 0.0
    return p3, ix, iz


def reference_fftpower(pos, BoxSize, Nmesh, Nmu, kmin=0.0):
    """P(k, mu) and its mode counts: CIC deposit, ``np.fft.rfftn``, the
    compensation, and (k, mu) binning on the edges of
    :func:`shell_thresholds`; Hermitian pairs count twice."""
    N = int(Nmesh)
    p3, ix, iz = power_3d(cic_density(pos, BoxSize, N), BoxSize)
    isq = (ix[:, None, None] ** 2 + ix[None, :, None] ** 2
           + iz[None, None, :] ** 2)
    knorm = np.sqrt(isq.astype('f8'))
    mu = np.where(isq == 0, 0.0,
                  iz[None, None, :] / np.where(isq == 0, 1.0, knorm))
    wgt = np.where((iz == 0) | (iz == N // 2), 1.0, 2.0)
    wgt = np.broadcast_to(wgt[None, None, :], p3.shape)

    q = shell_thresholds(N, BoxSize, kmin)
    nk = len(q) - 1
    kbin = np.searchsorted(q, isq, side='right') - 1
    muedges = np.linspace(-1, 1, Nmu + 1)
    mubin = np.minimum(np.digitize(mu, muedges) - 1, Nmu - 1)
    keep = ((kbin >= 0) & (kbin < nk)).reshape(-1)
    flat = (kbin * Nmu + mubin).reshape(-1)[keep]

    def hist(v):
        return np.bincount(flat, weights=v.reshape(-1)[keep],
                           minlength=nk * Nmu).reshape(nk, Nmu)

    modes = hist(wgt)
    with np.errstate(invalid='ignore', divide='ignore'):
        return {'modes': modes, 'power': hist(wgt * p3) / modes}
