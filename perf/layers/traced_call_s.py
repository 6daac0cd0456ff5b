"""Layer "device": the wall of a call of window (a), the calls that
every other per-layer metric describes: the window from the first
call's start to the last call's end over its calls.  The guard on all
of them, to be read beside ``call_s`` (the untraced window): where the
two differ, the per-layer metrics describe another call than the one
that is timed (PERF.md section 7: ``desi_like_n512.lab``)."""


def read(ctx):
    x = ctx['xplane']
    if not x or not x['ncalls'] or x['window_from'] != 'call_annotations':
        return None
    return x['window_s'] / float(x['ncalls'])
