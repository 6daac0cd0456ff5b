"""Layer "host dispatch": the share of ``host_gap_s``, in %, whose gap
began while the host was under no ``nbk.`` scope but a call's root
(``fftpower.run``, ``convpower.run``, ``paircount.run``,
``serve.request``), over all host lines, innermost scope, window (a).
The guard on the library's host ledger, as ``unscoped_device_share`` is
on the device's side: it rises when host code runs between the scopes
again."""

from perf.lib import hostledger


def read(ctx):
    red = hostledger.gaps(ctx)
    if not red:
        return None
    return 100.0 * red['unscoped_s'] / red['gap_s']
