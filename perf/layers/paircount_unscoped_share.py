"""Layer "device": the share of the device's busy time, in %, spent in
ops under none of the library's layer scopes and none of the pair
counter's own (``nbk.paircount.grid``, ``.tiles``), window (a), first
device.  ``perf/lib/scopes.py:LAYERS`` does not know the ``paircount.``
scopes, so ``unscoped_device_share`` would count the whole call; this
is the guard on the tracing for the pair-counting cell.
``paircount.run`` is the call's root: what runs under it alone is
unscoped."""

from perf.lib import scopes

ROOT = 'paircount.run'
PREFIX = 'paircount.'


def read(ctx):
    red = scopes.of_run(ctx)
    if scopes.unreadable(red):
        return None
    mine = [v['device_s'] for k, v in red['scopes'].items()
            if k.startswith(PREFIX) and k != ROOT]
    if not mine:
        return None
    bare = red['layers'].get(scopes.UNSCOPED, 0.0) - sum(mine)
    if bare < -1e-9 * red['busy_s']:
        # more under the pair counter's scopes than under no layer's:
        # a miscount, and no guard (the rate goes with it)
        return None
    return 100.0 * max(bare, 0.0) / red['busy_s']
