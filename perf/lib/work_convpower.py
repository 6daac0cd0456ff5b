"""Bytes the survey call's own passes must move, from its shapes
alone: the numerators of ``ylm_hbm_share`` and
``convpower_fft_roofline``.  Beside ``work.py``, which a PR may not
edit."""

from perf.lib import manifest


def poles_of(cell):
    """The multipoles a survey cell asks for: its traffic file's."""
    return manifest.load_json(
        'perf', 'traffic', cell['traffic'] + '.json')['call']['poles']


def nfft(poles):
    """Forward transforms a ``ConvolvedFFTPower`` call makes: 2 ell + 1
    for each multipole, and one for the monopole's ``A_0`` whether or
    not it is asked for."""
    return sum(2 * ell + 1 for ell in set(poles) | {0})


def ylm_bytes(nmesh, poles, itemsize=4):
    """Least bytes the Ylm passes of one call move.  Each ``(ell, m)``
    term with ell > 0 reads the real density and writes it weighted by
    ``Y_lm(x^)`` (2 real fields), then reads the transform and the
    running ``A_ell`` and writes ``A_ell`` back (3 half-complex
    fields); the unit vectors are rebuilt from axis vectors.  At 512^3
    and poles 0, 2, 4: 14 x 2.69 GB."""
    n = int(nmesh)
    real = n ** 3 * itemsize
    cplx = n * n * (n // 2 + 1) * 2 * itemsize
    terms = sum(2 * ell + 1 for ell in set(poles) if ell > 0)
    return terms * (2 * real + 3 * cplx)
