"""CPU rehearsal of ``chip_smoke.py`` (on-chip-measurement guide,
section 2, rehearsals 1 and 2): its phases are functions of their
sizes, called here at 32^3 on the CPU — the flow, the arguments and the
checks, not the chip.  The script itself has no size or platform
option, and run as a program it must refuse the CPU."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BOX = {'boxsize': 500.0, 'nbar': 8e-4}      # 1e5 particles


@pytest.fixture(autouse=True)
def no_x64():
    # the script runs without x64 (the suite turns it on)
    with jax.enable_x64(False):
        yield


def test_device_phase():
    rec = chip_smoke.phase_device(matmul=256, reps=4)
    assert rec['platform'] == 'cpu' and rec['complex64_d2h']
    assert rec['bytes_limit'] is None       # the CPU reports none


def test_oracle_phase_agrees_with_numpy():
    rec = chip_smoke.phase_oracle(nmesh=32, boxsize=500.0, nbar=4e-4)
    assert rec['max_rel_err_2d'] < 1e-4
    assert rec['modes'] > 0


def test_oracle_catches_a_wrong_answer(monkeypatch):
    # the reference is independent: a library that skipped the
    # compensation would not pass it
    from nbodykit_tpu.source.mesh import catalog
    monkeypatch.setattr(
        catalog, 'compensation_transfer',
        lambda *a: (lambda w, v: v), raising=True)
    with pytest.raises(AssertionError, match='oracle'):
        chip_smoke.phase_oracle(nmesh=32, boxsize=500.0, nbar=4e-4)


def test_fftpower_phase():
    rec = chip_smoke.phase_fftpower(nmesh=32, min_modes=500, **BOX)
    assert abs(rec['p0_over_shot_mean'] - 1) < 0.05
    assert rec['cold_wall_s'] > 0 and rec['warm_wall_s'] > 0


def test_serve_phase():
    # one device, one worker, as on the one-chip machine (with more
    # lanes an idle one may steal a ticket and pay its own compile)
    from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
    with use_mesh(cpu_mesh(1)):
        rec = chip_smoke.phase_serve(nmesh=32, npart=100000,
                                     hbm_bytes=16e9, deadline_s=300.0)
    assert rec['summary']['completed'] == 3
    assert rec['compile_misses'][1] == rec['compile_misses'][0]


def test_multichip_phase_on_four_virtual_devices(monkeypatch):
    # with the TPU-shaped branch of every is_mxu_backend() dispatch
    # (MXU histogram, radix ordering, exchange routing): inside
    # shard_map those had never run before the first four-chip call,
    # which found a replicated loop carry in the MXU histogram
    import nbodykit_tpu.utils
    from nbodykit_tpu.parallel.runtime import cpu_mesh
    monkeypatch.setattr(nbodykit_tpu.utils, 'is_mxu_backend',
                        lambda: True)
    rec = chip_smoke.phase_multichip(
        cpu_mesh(1), cpu_mesh(4), nmesh=16, nmesh_full=32,
        min_modes=100, **BOX)
    assert rec['ndevices'] == 4 and len(set(rec['shard_devices'])) == 4
    assert rec['max_rel_diff_1_vs_n'] < 1e-4
    assert rec['full']['nmesh'] == 32


def test_the_program_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run([sys.executable,
                          os.path.join(REPO, 'chip_smoke.py')],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert 'needs 1 TPU device' in out.stderr
    # no phase ran and no result was printed
    assert out.stdout.strip() == ''
