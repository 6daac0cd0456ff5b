"""Layer "ylm passes": device self time a call of the ops whose
innermost scope is ``nbk.convpower.ylm``
(``algorithms/convpower/fkp.py:_ell_program``: the density weighted by
``Y_lm(x^)`` before each transform, the transform weighted by
``Y_lm(k^)`` and added to ``A_ell`` after it), window (a), first
device.  The transforms between them stay ``fft_device_s``, the
compensation ``transfer_device_s``.  ``None`` where the program names
no such scope."""

from perf.lib import scopes

SCOPE = 'convpower.ylm'


def read(ctx):
    red = scopes.of_run(ctx)
    if scopes.unreadable(red) or SCOPE not in red['scopes']:
        return None
    return red['scopes'][SCOPE]['device_s']
