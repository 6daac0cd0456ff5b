"""Layer "paint kernel": the bytes a TSC scatter of the survey's data
and randoms must move (``perf/lib/work.py:paint_bytes``: 228 B a
particle, 27 cells read and written) over ``paint_device_s``, as a
share of the chip's published HBM bandwidth, in %."""

from perf.lib import scopes
from perf.lib.peaks import peaks_for
from perf.lib.work import paint_bytes


def read(ctx):
    t = scopes.layer_s(ctx, 'paint')
    c = ctx['config']
    if not t or 'randoms_per_data' not in c:
        return None
    npart = c['N'] * (1 + c['randoms_per_data'])
    peak = peaks_for(ctx['device_kind'])['hbm_bytes_per_s'] * ctx['chips']
    return 100.0 * paint_bytes(npart, c['resampler']) / t / peak
