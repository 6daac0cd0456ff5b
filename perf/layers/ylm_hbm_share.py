"""Layer "ylm kernel": the bytes the Ylm passes of one call must move
(``perf/lib/work_convpower.py:ylm_bytes``: per (ell, m) a read and a
write of the real field, two reads and a write of the half-complex
one) over ``ylm_device_s``, as a share of the chip's published HBM
bandwidth, in %.  Withheld like ``fft_roofline`` while
``convpower_unscoped_share`` is above 10%."""

from perf.layers.convpower_unscoped_share import read as unscoped_share
from perf.layers.ylm_device_s import read as ylm_device_s
from perf.lib import scopes
from perf.lib.peaks import peaks_for
from perf.lib.work_convpower import poles_of, ylm_bytes


def read(ctx):
    t, share = ylm_device_s(ctx), unscoped_share(ctx)
    if not t or share is None or share > scopes.UNSCOPED_MAX:
        return None
    peak = peaks_for(ctx['device_kind'])['hbm_bytes_per_s'] * ctx['chips']
    return 100.0 * ylm_bytes(ctx['config']['Nmesh'],
                             poles_of(ctx['cell'])) / t / peak
