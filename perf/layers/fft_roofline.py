"""Layer "fft kernel": the bytes one r2c of Nmesh^3 must move
(``perf/lib/work.py:r2c_bytes``) over ``fft_device_s``, as a share of
the published HBM bandwidth times the chips, in %: HBM-bound (an FFT
of 512^3 does 5 N^3 log2 N^3 = 1.8e10 flops against 3.2e9 bytes; at
197 TFLOP/s and 819 GB/s the bytes take forty times longer).  Withheld
while ``unscoped_device_share`` is above 10%: an FFT whose ops lost
their scope would read faster than it is."""

from perf.lib import scopes
from perf.lib.peaks import peaks_for
from perf.lib.work import r2c_bytes


def read(ctx):
    t = scopes.layer_s(ctx, 'fft')
    share = scopes.unscoped_share(ctx)
    if not t or share is None or share > scopes.UNSCOPED_MAX:
        return None
    peak = peaks_for(ctx['device_kind'])['hbm_bytes_per_s'] * ctx['chips']
    return 100.0 * r2c_bytes(ctx['config']['Nmesh']) / t / peak
