"""Layer "device": seconds a call keeps the device busy — the union of
the op intervals in window (a) over the calls made in it, on the device
``device_idle_share`` is read from (the one that idled most)."""


def read(ctx):
    x = ctx['xplane']
    if not x or not x['ncalls']:
        return None
    worst = max(x['devices'].values(), key=lambda d: d['idle_share'])
    return worst['busy_s'] / x['ncalls']
