"""Layer "paint kernel": the bytes a CIC scatter of N particles must
move (``perf/lib/work.py:paint_bytes``) over ``paint_s``, as a share
of the chip's published HBM bandwidth, in %.  On several chips each
paints its share of the particles, so the peak is times the chips."""

from perf.layers.paint_s import read as paint_s
from perf.lib.peaks import peaks_for
from perf.lib.work import paint_bytes


def read(ctx):
    t = paint_s(ctx)
    if not t:
        return None
    peak = peaks_for(ctx['device_kind'])['hbm_bytes_per_s'] * ctx['chips']
    return 100.0 * paint_bytes(ctx['config']['N'], 'cic') / t / peak
