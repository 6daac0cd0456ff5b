"""Layer "host dispatch": seconds a call the host worked, from the
library's own ring of calls (``diagnostics.HOST_CALLS``, kept with no
instrument on): median over window (a)'s records, selected by time,
of ``wall_s - sync_wait_s``: the call's wall less what it waited in
fetches.  ``None`` where the program keeps no ring."""

from perf.lib import hostledger


def read(ctx):
    return hostledger.host_work_s(ctx)
