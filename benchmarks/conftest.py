"""Benchmark suite configuration.

Mirrors the reference's ``benchmarks/`` pytest harness
(``/root/reference/benchmarks/conftest.py:25-29``): a session fixture
defines the sample scales and a ``benchmark(name)`` context manager
times labelled phases, appending one JSON record per test to
``BENCH_DIR`` (env, default ``./.bench_results``).

Scale is chosen with ``--bench-scale`` (default ``test`` so the suite
is cheap enough for CPU CI; ``boss_like``/``desi_like``/``dm_like``
are the reference's production scales for TPU runs).
"""

import contextlib
import json
import os
import time

import pytest

# (BoxSize, Nmesh, N) — the reference's sample definitions
SCALES = {
    'test': dict(BoxSize=100.0, Nmesh=64, N=1000),
    'boss_like': dict(BoxSize=2500.0, Nmesh=1024, N=int(1e6)),
    'desi_like': dict(BoxSize=5000.0, Nmesh=1024, N=int(1e7)),
    'dm_like': dict(BoxSize=5000.0, Nmesh=1024, N=512 ** 3),
}


def pytest_addoption(parser):
    parser.addoption('--bench-scale', default='test',
                     choices=sorted(SCALES),
                     help='benchmark sample scale')


def pytest_configure(config):
    # CPU unless the run EXPLICITLY opts into the TPU with
    # BENCH_PLATFORM=tpu: a harness run must never take the chip by
    # accident.
    import jax
    if os.environ.get('BENCH_PLATFORM', 'cpu') != 'tpu':
        jax.config.update('jax_platforms', 'cpu')


@pytest.fixture(scope='session')
def sample(request):
    """The benchmark sample scale (reference BenchmarkingSample)."""
    name = request.config.getoption('--bench-scale')
    s = dict(SCALES[name])
    s['name'] = name
    return s


@pytest.fixture
def benchmark(request):
    """``with benchmark('Data'): ...`` labelled phase timer; results
    land in $BENCH_DIR/<test_name>.json (reference timing blocks,
    benchmarks/test_fftpower.py:7-19)."""
    records = {}

    @contextlib.contextmanager
    def timer(name):
        t0 = time.time()
        yield
        records[name] = round(time.time() - t0, 4)

    yield timer

    if records:
        outdir = os.environ.get('BENCH_DIR', '.bench_results')
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, request.node.name + '.json')
        with open(path, 'w') as f:
            json.dump({'test': request.node.name, 'phases': records,
                       'at': time.strftime('%Y-%m-%dT%H:%M:%SZ',
                                           time.gmtime())}, f)
