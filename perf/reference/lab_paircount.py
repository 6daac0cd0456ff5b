"""The plain reference of the ``lab_paircount`` kind: cumulative pair
counts in a periodic cube, numpy in f8 and int64, sharing no code with
``nbodykit_tpu``.

``C(r)`` here is the number of ordered pairs ``i != j`` whose
minimum-image separation is at most ``r``.  Two ways to it:

- :func:`brute_cumulative`: every pair, a block of rows at a time
  (the oracle's 2e4 points: 4e8 separations, seconds); with
  ``quantize`` the coordinate differences pass through a coarser
  format first;
- :func:`tree_cumulative`: ``scipy.spatial.cKDTree.count_neighbors``
  with ``boxsize`` (the timed call's 1.2e6 points and 30 radii: about
  a minute of one core).

:func:`bracket` of ``C`` at :func:`bracket_radii` is what ``correct``
compares a result with."""

import numpy as np


def round_to_bfloat16(x):
    """f8 values rounded to bfloat16's 8 significant bits (to nearest,
    ties to even, by the bit pattern of their f4), as f8."""
    b = np.asarray(x, 'f4').view('u4').astype('u8')
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype('u4').view('f4').astype('f8')


def brute_cumulative(pos, boxsize, radii, quantize=None, block=128):
    """``C(r)`` for every ``r`` of ``radii``, all pairs.  With
    ``quantize`` (which may move a separation by 2% at most) also the
    same count with the coordinate differences passed through it, from
    the same pass: ``(C, C_quantized)``."""
    pos = np.asarray(pos, 'f8') % boxsize
    r2 = np.asarray(radii, 'f8') ** 2
    n = len(pos)
    out = np.zeros((2, len(r2)), 'i8')

    def images(a, b):
        # the minimum image's length: only its square is wanted
        d = np.abs(a - b)
        return np.minimum(d, boxsize - d, out=d)

    for start in range(0, n, block):
        rows = np.arange(start, min(start + block, n))
        sep2 = np.zeros((len(rows), n))
        for axis in range(3):
            d = images(pos[rows, None, axis], pos[None, :, axis])
            d *= d
            sep2 += d
        # not a point with itself
        sep2[rows - start, rows] = np.inf
        near = sep2[sep2 <= r2.max()]
        out[0] += np.searchsorted(np.sort(near), r2, side='right')
        if quantize is not None:
            i, j = np.nonzero(sep2 <= 1.05 ** 2 * r2.max())
            near = (quantize(images(pos[rows[i]], pos[j])) ** 2
                    ).sum(axis=-1)
            out[1] += np.searchsorted(np.sort(near), r2, side='right')
    return out[0] if quantize is None else (out[0], out[1])


def tree_cumulative(pos, boxsize, radii):
    """``C(r)`` for every ``r`` of ``radii``, by a kd-tree's dual
    count; the points wrapped into [0, boxsize)."""
    from scipy.spatial import cKDTree
    pos = np.asarray(pos, 'f8') % boxsize
    tree = cKDTree(pos, boxsize=boxsize)
    # the tree counts a point with itself, at separation 0
    return tree.count_neighbors(tree, np.asarray(radii, 'f8')
                                ).astype('i8') - len(pos)


def bracket_radii(edges, delta):
    """Where :func:`bracket` wants ``C``: ``e (1 - delta)`` of every
    edge, then ``e (1 + delta)`` of every edge."""
    edges = np.asarray(edges, 'f8')
    return np.concatenate([edges * (1 - delta), edges * (1 + delta)])


def bracket(counts):
    """``(lo, hi)`` for the counts since the first edge, ``#{e_0 <= r
    < e_j}``, of a result whose separations are good to ``delta``
    (relative), from ``counts = C(bracket_radii(edges, delta))``:

        lo_j = C(e_j (1 - delta)) - C(e_0 (1 + delta))
        hi_j = C(e_j (1 + delta)) - C(e_0 (1 - delta))."""
    counts = np.asarray(counts, 'i8')
    inner, outer = counts[:len(counts) // 2], counts[len(counts) // 2:]
    return inner - outer[0], outer - inner[0]


def shell_means(n, boxsize, edges):
    """Expected ordered pairs of ``n`` uniform points in each bin:
    ``n (n - 1) V_shell / V``."""
    edges = np.asarray(edges, 'f8')
    shell = 4.0 / 3.0 * np.pi * np.diff(edges ** 3)
    return n * (n - 1.0) * shell / float(boxsize) ** 3
