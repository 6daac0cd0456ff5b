"""Layer "host dispatch": ``nbk.sync.*`` annotations begun inside the
calls of window (a), per call: the deliberate device-to-host fetches
of one call (``diagnostics.fetch``), each of which drains the queue.
``None`` where the program marks no fetch at all."""

from perf.lib import hostledger


def read(ctx):
    red = hostledger.sync_marks(ctx)
    return None if red is None else red['syncs']
