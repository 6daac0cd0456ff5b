"""Layer "device": the share of the device's busy time, in %, spent in
ops under none of the library's layer scopes (paint, fft, transfer,
binning, exchange, a2a), window (a), first device.  The guard on the
tracing itself: it rises when a refactor drops a scope."""

from perf.lib import scopes


def read(ctx):
    return scopes.unscoped_share(ctx)
