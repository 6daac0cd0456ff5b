"""Layer "exchange": device self time a call of the ops under
``nbk.exchange`` (``parallel/exchange.py:exchange_by_dest``: the
bucketing by destination, its three all_to_alls and the ``psum`` of
the dropped count), window (a), first device.  Exists only across
chips: ``None`` on one."""

from perf.lib import scopes


def read(ctx):
    if ctx['chips'] < 2:
        return None
    return scopes.layer_s(ctx, 'exchange')
