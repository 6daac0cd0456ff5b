"""Layer "host dispatch": seconds a call of ``host_gap_s`` whose gap
began under an ``nbk.sync.*`` annotation (``diagnostics.fetch``),
window (a): the round trip after a fetch, the host waiting for a value
and the device with nothing queued behind it.  ``None`` where the
program marks no fetch at all."""

from perf.lib import hostledger


def read(ctx):
    red = hostledger.sync_marks(ctx)
    return None if red is None else red['sync_s']
