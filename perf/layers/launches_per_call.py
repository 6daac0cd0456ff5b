"""Layer "host dispatch": program executions on the first device (the
events of its ``XLA Modules`` line that start inside window (a)) per
call.  The eager lab path launches one program per library op; the
served path one fused program per request."""


def read(ctx):
    x = ctx['xplane']
    if not x or not x['ncalls']:
        return None
    first = x['devices'][min(x['devices'])]
    return first['launches'] / float(x['ncalls'])
