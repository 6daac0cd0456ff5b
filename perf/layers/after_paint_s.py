"""Layer "FFT + transfer + binning": end(``fftpower.binning``) -
end(``paint``) in window (b), median, seconds a call.  The spans
``mesh.r2c`` / ``fft.r2c`` close on the enqueue, so the r2c, the
compensation and |delta_k|^2 drain inside ``fftpower.binning``'s wait:
that span alone is not the binning's time, but everything after the
paint ends with it."""

import statistics


def read(ctx):
    spans = ctx.get('spans') or ()
    ends = {name: sorted(r['ts'] + r['dur'] for r in spans
                         if r['name'] == name)
            for name in ('paint', 'fftpower.binning')}
    pairs = list(zip(ends['paint'], ends['fftpower.binning']))
    if not pairs or len(ends['paint']) != len(ends['fftpower.binning']):
        return None
    return statistics.median(b - p for p, b in pairs)
