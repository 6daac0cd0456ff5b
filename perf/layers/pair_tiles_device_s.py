"""Layer "pair tiles": device self time a call of the ops whose
innermost scope is ``nbk.paircount.tiles``
(``algorithms/pair_counters/core.py``: the row reads, the dense tiles
of squared separations, the cumulative compares and their sums),
window (a), first device.  ``None`` where the program names no such
scope."""

from perf.layers.pair_grid_device_s import scope_device_s

SCOPE = 'paircount.tiles'


def read(ctx):
    return scope_device_s(ctx, SCOPE)
