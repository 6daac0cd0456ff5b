"""Layer "pair kernel": the flops the pairs within rmax must cost
(``perf/lib/work_paircount.py:pair_flops``: eight a pair, 1.27e9
pairs at the cell's size) over ``pair_tiles_device_s``, as a share of
the chip's published bf16 peak, in %: the only compute peak
``peaks.json`` has.  The separations are f32 work on the vector
units, which have no published peak, so the share is small by
construction and cannot pass 100%; it moves with the kernel's time
and nothing else.  Withheld while ``paircount_unscoped_share`` is
above 10%: a kernel whose ops lost their scope would read faster than
it is."""

from perf.layers.pair_tiles_device_s import read as tiles_s
from perf.layers.paircount_unscoped_share import read as unscoped_share
from perf.lib import scopes
from perf.lib.peaks import peaks_for
from perf.lib.work_paircount import pair_flops


def read(ctx):
    t, share = tiles_s(ctx), unscoped_share(ctx)
    if not t or share is None or share > scopes.UNSCOPED_MAX:
        return None
    c = ctx['config']
    peak = peaks_for(ctx['device_kind'])['bf16_flops_per_s'] * ctx['chips']
    return 100.0 * pair_flops(c['N'], c['BoxSize'], c['rmax']) / t / peak
