"""The served FFTPower estimator in plain numpy.

The plain reference of the ``served_closed_loop`` traffic kind: what
``nbodykit_tpu/serve/scheduler.py`` computes for an
``AnalysisRequest(algorithm='FFTPower')``, written from its definition
and sharing no code with the library: CIC deposit, ``rfftn``, the
compensation, and integer-lattice shells of width 2 pi / BoxSize,
``nmesh // 2`` of them, shell b taking floor(|i|) == b and the last
one everything beyond (the corners of the cube past the Nyquist
sphere); Hermitian pairs count twice, the DC mode is cleared and left
out of shell 0's count."""

import numpy as np

from perf.reference.lab_fftpower import cic_density, power_3d


def served_thresholds(nmesh):
    """Shell b holds q[b] <= |i|^2 < q[b+1]; the last shell is open."""
    nb = int(nmesh) // 2
    q = np.arange(nb + 1, dtype='i8') ** 2
    q[-1] = 3 * (int(nmesh) // 2 + 1) ** 2 + 1
    return q


def reference_served(pos, BoxSize, nmesh):
    """(P(k), modes) per shell as the served program returns them."""
    N = int(nmesh)
    p3, ix, iz = power_3d(cic_density(pos, BoxSize, N), BoxSize)
    isq = (ix[:, None, None] ** 2 + ix[None, :, None] ** 2
           + iz[None, None, :] ** 2)
    wgt = np.where((iz == 0) | (iz == N // 2), 1.0, 2.0)
    wgt = np.broadcast_to(wgt[None, None, :], p3.shape)
    q = served_thresholds(N)
    nb = len(q) - 1
    shell = (np.searchsorted(q, isq, side='right') - 1).reshape(-1)
    modes = np.bincount(shell, weights=wgt.reshape(-1), minlength=nb)
    power = np.bincount(shell, weights=(wgt * p3).reshape(-1),
                        minlength=nb) / np.maximum(modes, 1.0)
    modes[0] -= 1.0                     # the DC mode
    return power, modes
