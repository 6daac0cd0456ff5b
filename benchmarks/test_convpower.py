"""ConvolvedFFTPower benchmark (reference
benchmarks/test_convpower.py:7-25): FKP catalog with 10x randoms,
poles [0, 2, 4], dk=0.005."""

import numpy as np


def uniform_catalog(npart, BoxSize, seed):
    """``npart`` uniform particles in the box, as the benchmark cell's
    driver makes them (``perf/drivers/lab_fftpower.py:make_catalog``):
    ``UniformCatalog``'s own draw with the count fixed.
    ``UniformCatalog(nbar, BoxSize)`` takes its count from Poisson(nbar
    V) of the seed, so every seed has other shapes and pays its own
    compiles (~130 s on the chip); upstream's sample is defined by N."""
    from nbodykit_tpu.lab import RandomCatalog
    from nbodykit_tpu.utils import working_dtype
    cat = RandomCatalog(int(npart), seed=seed)
    box = np.full(3, float(BoxSize))
    cat.attrs['BoxSize'] = box
    wdt = working_dtype('f8')
    cat['Position'] = (cat.rng.uniform(itemshape=(3,), dtype=wdt)
                       * box).astype(wdt)
    return cat


def test_convpower(sample, benchmark):
    from nbodykit_tpu.algorithms.convpower import (FKPCatalog,
                                                   ConvolvedFFTPower)

    nbar = sample['N'] / sample['BoxSize'] ** 3
    with benchmark('Data'):
        data = uniform_catalog(sample['N'], sample['BoxSize'], seed=42)
        randoms = uniform_catalog(10 * sample['N'], sample['BoxSize'],
                                  seed=84)
        data['NZ'] = nbar * np.ones(data.size)
        randoms['NZ'] = nbar * np.ones(randoms.size)
        fkp = FKPCatalog(data, randoms)
        mesh = fkp.to_mesh(Nmesh=sample['Nmesh'], resampler='tsc')

    with benchmark('Algorithm'):
        r = ConvolvedFFTPower(mesh, poles=[0, 2, 4], dk=0.005)
        assert np.isfinite(
            np.asarray(r.poles['power_0'].real)).any()
