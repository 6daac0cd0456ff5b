"""Layer "device": the share of the device's busy time, in %, spent in
ops under none of the library's layer scopes and none of the survey
path's own (``nbk.convpower.ylm``, ``.density``, ``.stats``), window
(a), first device.  ``perf/lib/scopes.py:LAYERS`` does not know the
``convpower.`` scopes, so ``unscoped_device_share`` would count the Ylm
passes; this is the guard on the tracing for the survey cell.
``convpower.run`` is the call's root: what runs under it alone is
unscoped."""

from perf.lib import scopes

ROOT = 'convpower.run'
PREFIX = 'convpower.'


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
        # more under the survey path's scopes than under no layer's:
        # a miscount, and no guard (the rooflines go with it)
        return None
    return 100.0 * max(bare, 0.0) / red['busy_s']
