"""Bytes a kernel must move, from its shapes alone.

The numerators of the ``*_hbm_share`` metrics.  Kept with the
benchmark so that no PR that claims a gain can change them."""

#: cells a particle touches per axis, by window
_SUPPORT = {'nnb': 1, 'cic': 2, 'tsc': 3, 'pcs': 4}


def paint_bytes(npart, resampler='cic', itemsize=4):
    """Least bytes a scatter paint of ``npart`` particles moves.

    Each particle reads its position (3 words) and, for each of the
    support^3 cells it touches, reads the cell and writes it back
    (2 words): 12 + 8 * (4 + 4) = 76 bytes a particle for CIC in f4.
    The mesh's own zero fill and final read are left out: they do not
    grow with the particles."""
    cells = _SUPPORT[resampler] ** 3
    return int(npart) * itemsize * (3 + 2 * cells)


def r2c_bytes(nmesh, itemsize=4):
    """Least bytes a real-to-complex 3-d FFT of ``nmesh``^3 moves.

    One pass per axis, each reading and writing the field once; the
    first reads nmesh^3 reals and writes nmesh^2 (nmesh/2 + 1) complex
    numbers, the other two read and write that complex field:
    nmesh^3 * w + 5 * nmesh^2 (nmesh/2 + 1) * 2w."""
    n = int(nmesh)
    cplx = n * n * (n // 2 + 1) * 2 * itemsize
    return n ** 3 * itemsize + 5 * cplx
