"""Layer "all_to_all kernel": the bytes one chip must send in the slab
r2c's transpose (``perf/lib/ici.py:a2a_bytes``) over ``a2a_device_s``,
as a share of the published interchip bandwidth of one chip, in %.
That figure is a chip's total over all its links and a 2x2 host wires
half of them, so the reading is conservative.  Withheld, like
``fft_roofline``, while ``unscoped_device_share`` is above 10% (an
all_to_all that lost its scope would read faster than it is) or no op
ran under the scope."""

from perf.lib import scopes
from perf.lib.ici import ICI_BYTES_PER_S, a2a_bytes


def read(ctx):
    if ctx['chips'] < 2:
        return None
    t = scopes.layer_s(ctx, 'a2a')
    share = scopes.unscoped_share(ctx)
    if not t or share is None or share > scopes.UNSCOPED_MAX:
        return None
    sent = a2a_bytes(ctx['config']['Nmesh'], ctx['chips'])
    return 100.0 * sent / t / ICI_BYTES_PER_S[ctx['device_kind']]
