"""Layer "paint": median duration of the library's span ``paint`` in
window (b), seconds a call.  The span is synchronised
(``pmesh.py``: ``block_until_ready`` inside it) only while the tracer
is on, which is why window (b) exists."""

import statistics


def durations(ctx):
    return [r['dur'] for r in ctx.get('spans') or ()
            if r['name'] == 'paint']


def read(ctx):
    d = durations(ctx)
    return statistics.median(d) if d else None
