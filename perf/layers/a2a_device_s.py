"""Layer "fft all_to_all": device self time a call of the ops under
``nbk.fft.a2a.<axis>`` (``parallel/dfft.py:_a2a_site``: the transpose
between the slab FFT's local passes), window (a), first device; what
``fft_device_s`` leaves out.  Exists only across chips: ``None`` on
one."""

from perf.lib import scopes


def read(ctx):
    if ctx['chips'] < 2:
        return None
    return scopes.layer_s(ctx, 'a2a')
