"""Layer "host dispatch": seconds a call in which the first device had
nothing to run while a call was open, window (a):
``perf/lib/scopes.py``'s idle gaps less those that began between two
calls.  What the host's half of the call costs the device;
``unscoped_gap_share`` and ``sync_gap_s`` split it by the scope the
host was under (``perf/lib/hostledger.py``)."""

from perf.lib import scopes

BETWEEN = 'between_calls'


def read(ctx):
    red = scopes.of_run(ctx)
    if not red:
        return None
    return sum(v for k, v in red['idle_gaps'].items() if k != BETWEEN)
