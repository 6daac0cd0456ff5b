"""Layer "fft kernel": the bytes the forward transforms of one survey
call must move (``work_convpower.nfft(poles)`` of
``perf/lib/work.py:r2c_bytes``: 15 r2c for poles 0, 2, 4) over
``fft_device_s``, as a share of the chip's published HBM bandwidth, in
%.  ``fft_roofline`` divides one r2c's bytes and cannot be listed for
this cell.  Withheld while ``convpower_unscoped_share`` is above 10%:
an FFT whose ops lost their scope would read faster than it is."""

from perf.layers.convpower_unscoped_share import read as unscoped_share
from perf.lib import scopes
from perf.lib.peaks import peaks_for
from perf.lib.work import r2c_bytes
from perf.lib.work_convpower import nfft, poles_of


def read(ctx):
    t, share = scopes.layer_s(ctx, 'fft'), unscoped_share(ctx)
    if not t or share is None or share > scopes.UNSCOPED_MAX:
        return None
    peak = peaks_for(ctx['device_kind'])['hbm_bytes_per_s'] * ctx['chips']
    return 100.0 * nfft(poles_of(ctx['cell'])) * r2c_bytes(
        ctx['config']['Nmesh']) / t / peak
