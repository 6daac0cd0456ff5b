"""Layer "device": the share of window (a) in which no operation ran
on the device, in %, on the device that idled most.  From the
profiler's trace: 1 - union of the device's op intervals / window."""


def read(ctx):
    x = ctx['xplane']
    if not x:
        return None
    return 100.0 * max(d['idle_share'] for d in x['devices'].values())
