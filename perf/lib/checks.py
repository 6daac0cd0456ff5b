"""What decides ``correct``, and the readings every driver shares.

Copied from ``chip_smoke.py`` (PR 22, which ran them on the chip) so
that the yardstick lives under ``perf/``: the device record, the peak
memory, the compile seconds, the integer-lattice mode count and the
shot-noise bound.  Nothing here is imported from ``chip_smoke.py`` or
``bench.py``."""

import numpy as np


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def device_record():
    """The device as JAX reports it — the contract's last line."""
    import jax
    d = jax.devices()[0]
    return {'platform': d.platform, 'kind': d.device_kind,
            'count': len(jax.devices())}


def bytes_limit():
    """Device 0's memory limit (None where the backend reports none)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get('bytes_limit')


def peak_bytes():
    """Largest ``peak_bytes_in_use`` over the local devices (None
    where the backend keeps no such statistic, as the CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use')
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compile_seconds():
    """Seconds the backend has spent compiling, or loading from the
    persistent cache, so far in this process (the
    ``xla.compile.backend_s`` histogram the library keeps)."""
    from nbodykit_tpu.diagnostics.metrics import REGISTRY
    snap = REGISTRY.snapshot().get('xla.compile.backend_s')
    return float(snap['sum']) if snap else 0.0


def counter_value(name):
    """A counter of the library's registry (0 before its first add)."""
    from nbodykit_tpu.diagnostics.metrics import REGISTRY
    snap = REGISTRY.snapshot().get(name)
    return int(snap['value']) if snap else 0


def lattice_mode_counts(nmesh, thresholds):
    """Modes per shell on the integer half lattice of an ``nmesh``^3
    real transform, Hermitian pairs counted twice: shell b holds
    thresholds[b] <= |i|^2 < thresholds[b+1].

    Counted without the lattice: r2[s] is the number of (ix, iy) with
    ix^2 + iy^2 = s, and for each iz the modes under a threshold q are
    the cumulative r2 below q - iz^2.  Milliseconds at 1024^3."""
    N = int(nmesh)
    q = np.asarray(thresholds, 'i8')
    ix = np.fft.fftfreq(N, 1.0 / N).astype('i8')
    r2 = np.bincount((ix[:, None] ** 2 + ix[None, :] ** 2).reshape(-1))
    below = np.concatenate([[0], np.cumsum(r2)])    # below[t]: s < t
    iz = np.arange(N // 2 + 1)
    wz = np.where((iz == 0) | (iz == N // 2), 1.0, 2.0)
    t = np.clip(q[None, :] - iz[:, None] ** 2, 0, len(r2))
    under = (below[t] * wz[:, None]).sum(axis=0)    # |i|^2 < q[b]
    return np.diff(under)


def check_shotnoise(p0, modes, shot, min_modes, what):
    """A flat spectrum at the shot noise on well-populated shells:
    each within 1% plus five standard deviations of a shell's estimate
    (a shell of M counted modes has M/2 independent ones, so sigma/P =
    sqrt(2/M): 2.4% in all at 1e5 modes), and their mode-weighted mean
    likewise.  Returns the mean and the worst shell, over the shot
    noise."""
    p0, modes = np.asarray(p0, 'f8'), np.asarray(modes, 'f8')
    well = modes >= min_modes
    check(well.sum() >= 3, '%s: no well-populated shells' % what)
    off = np.abs(p0[well] / shot - 1)
    worst = float(off.max())
    mean = float(np.sum(p0[well] * modes[well]) / modes[well].sum()
                 / shot)
    check(np.all(off < 0.01 + 5 * np.sqrt(2 / modes[well])),
          '%s: P off the shot noise by up to %.3g on shells with >= %d '
          'modes' % (what, worst, min_modes))
    check(abs(mean - 1) < 0.01 + 5 * np.sqrt(2 / modes[well].sum()),
          '%s: mode-weighted P / shot noise = %.4f' % (what, mean))
    return mean, worst
