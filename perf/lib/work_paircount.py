"""Operations the pair count must do, from its sizes alone: the
numerator of ``pair_rate_share``.  Beside ``work.py``, which a PR may
not edit."""

import math

#: three differences, three products, two sums: a squared separation
FLOPS_PER_PAIR = 8


def pair_flops(n, boxsize, rmax):
    """Least flops a count of the ordered pairs within ``rmax`` of
    ``n`` points in a periodic cube spends on separations: the pairs
    any implementation must weigh, ``n^2 (4/3) pi rmax^3 / V`` for a
    uniform catalog, eight flops each.  What a cell decomposition
    visits beyond the sphere (``pair_slots_per_pair``), the binning and
    the sums are left out: they are how, not what."""
    pairs = float(n) ** 2 * 4.0 / 3.0 * math.pi * float(rmax) ** 3 \
        / float(boxsize) ** 3
    return FLOPS_PER_PAIR * pairs
