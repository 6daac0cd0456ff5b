"""Layer "pair grid": device self time a call of the ops whose
innermost scope is ``nbk.paircount.grid``
(``algorithms/pair_counters/core.py``: the cell ids, the one sort that
carries the coordinates, the run edges and the block table), window
(a), first device.  ``None`` where the program names no such scope."""

from perf.lib import scopes

SCOPE = 'paircount.grid'


def scope_device_s(ctx, scope):
    """Device self time a call of the ops whose innermost scope is
    ``scope``, or ``None``."""
    red = scopes.of_run(ctx)
    if scopes.unreadable(red) or scope not in red['scopes']:
        return None
    return red['scopes'][scope]['device_s']


def read(ctx):
    return scope_device_s(ctx, SCOPE)
