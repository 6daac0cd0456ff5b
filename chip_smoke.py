"""The quickest proof that nbodykit-tpu still starts on the chip.

    python chip_smoke.py            one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  the four-chip path and what it is
                                    compared with, and no other phase

One process, the calls a user makes (``nbodykit_tpu.lab`` and the serve
plane), at the upstream suite's ``desi_like`` sample
(benchmarks/conftest.py: BoxSize 5000, Nmesh 1024, N 1e7).  One v5e
(15.75 GB) cannot hold the 1024^3 pipeline next to 1e7 particles
(``memory_plan(1024, 1e7)`` asks 17.3 GB), so on one chip the mesh is
cut to Nmesh=512 with N=1e7 kept; four chips run the published 1024.
Data are made on the device from a seed.

Each phase prints one JSON line; the first failure ends the run
non-zero.  There is no CPU carry-on: without a TPU the script exits 1
before any phase.  The walls it prints are those of one smoke run, not
a benchmark.  The phases are functions of their sizes so that
tests/test_chip_smoke.py can rehearse them on the CPU at 32^3.
"""

import argparse
import json
import sys
import time

import numpy as np

#: upstream ``desi_like`` (benchmarks/conftest.py:26): nbar * BoxSize^3
#: = 1e7 particles
DESI_LIKE = {'BoxSize': 5000.0, 'nbar': 8e-5, 'Nmesh': 1024}
#: what one 15.75 GB chip holds of it (see the module docstring)
ONE_CHIP_NMESH = 512
SEED = 42


def say(phase, **fields):
    print(json.dumps(dict(phase=phase, **fields), sort_keys=True),
          flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def device_record():
    """The device as JAX reports it — the contract's last line."""
    import jax
    d = jax.devices()[0]
    return {'platform': d.platform, 'kind': d.device_kind,
            'count': len(jax.devices())}


def peak_bytes():
    """Largest ``peak_bytes_in_use`` over the local devices (None
    where the backend keeps no such statistic, as the CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use')
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compile_seconds():
    """Seconds the backend has spent compiling so far in this process
    (the xla.compile.backend_s histogram the library keeps)."""
    from nbodykit_tpu.diagnostics.metrics import REGISTRY
    snap = REGISTRY.snapshot().get('xla.compile.backend_s')
    return float(snap['sum']) if snap else 0.0


# ---------------------------------------------------------------------------
# phase: device

def phase_device(matmul=4096, reps=40):
    """What the attached device is, and how two runtime basics behave
    that the library now leans on (``utils.as_numpy``, ``bench._sync``):
    complex64 transfers both ways, and whether a plain
    ``jax.block_until_ready`` waits for the work it is given."""
    import jax
    import jax.numpy as jnp
    rec = device_record()
    stats = jax.devices()[0].memory_stats() or {}
    rec['bytes_limit'] = stats.get('bytes_limit')

    c = jax.lax.complex(jnp.arange(8, dtype=jnp.float32),
                        -jnp.arange(8, dtype=jnp.float32))
    host = np.asarray(c)
    check(host.dtype == np.complex64
          and np.array_equal(host, np.arange(8) * (1 - 1j)),
          'complex64 device->host transfer returned %r' % (host,))
    rec['complex64_d2h'] = True
    back = np.asarray(jnp.asarray(host) * 2)
    check(np.array_equal(back, host * 2),
          'complex64 host->device transfer returned %r' % (back,))
    rec['complex64_h2d'] = True

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(
            0, reps, lambda i, y: (y @ x) * (1.0 / matmul), x)

    x = jnp.ones((matmul, matmul), jnp.float32)
    float(work(x)[0, 0])                    # compile + warm
    synced = []
    for _ in range(3):      # a scalar fetch is a real synchronisation
        t0 = time.perf_counter()
        float(work(x)[0, 0])
        synced.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    y = work(x)
    t1 = time.perf_counter()
    jax.block_until_ready(y)
    t2 = time.perf_counter()
    float(y[0, 0])
    t3 = time.perf_counter()
    rec.update(work_s=min(synced), dispatch_s=t1 - t0,
               block_until_ready_s=t2 - t1, fetch_after_s=t3 - t2)
    # it waited if it held the caller for about as long as the work
    rec['block_until_ready_waits'] = bool(t2 - t0 >= 0.5 * min(synced))
    check(rec['block_until_ready_waits'],
          'block_until_ready returned before the work was done: %r'
          % rec)
    return rec


# ---------------------------------------------------------------------------
# phase: oracle — the lab FFTPower against plain numpy

def shell_thresholds(Nmesh, BoxSize):
    """FFTPower's default k edges (kmin = 0, dk = 2 pi / BoxSize, up to
    the Nyquist frequency plus dk/2) as thresholds on the integer
    lattice: mode i lies in shell b iff q[b] <= |i|^2 < q[b+1].
    Modes sit exactly on these edges (every |i|^2 that is a perfect
    square), so the shell is decided in integers, never by how a
    square root rounds."""
    dk = 2 * np.pi / float(BoxSize)
    kedges = np.arange(0.0, np.pi * int(Nmesh) / float(BoxSize) + dk / 2,
                       dk)
    return np.ceil((kedges / dk) ** 2).astype('i8')


def reference_fftpower(pos, BoxSize, Nmesh, Nmu, poles):
    """FFTPower(mode='2d') of a uniform-weight catalog in plain numpy:
    CIC deposit, ``np.fft.rfftn``, the CIC compensation, and (k, mu)
    binning with dk = 2 pi / BoxSize from kmin = 0 — written from the
    estimator's definition, sharing no code with the library."""
    from numpy.polynomial.legendre import legval
    N, L = int(Nmesh), float(BoxSize)
    x = np.asarray(pos, 'f8') * (N / L)
    i0 = np.floor(x).astype('i8')
    f = x - i0
    field = np.zeros(N ** 3)
    for a in (0, 1):
        for b in (0, 1):
            for c in (0, 1):
                w = (np.abs(1 - a - f[:, 0]) * np.abs(1 - b - f[:, 1])
                     * np.abs(1 - c - f[:, 2]))
                lin = ((((i0[:, 0] + a) % N) * N
                        + (i0[:, 1] + b) % N) * N + (i0[:, 2] + c) % N)
                field += np.bincount(lin, weights=w, minlength=N ** 3)
    field = field.reshape(N, N, N) / (len(x) / float(N ** 3))

    ck = np.fft.rfftn(field) / N ** 3
    ix = np.fft.fftfreq(N, 1.0 / N).astype('i8')
    iz = np.arange(N // 2 + 1)
    for ax, i in enumerate((ix, ix, iz)):
        w = 2 * np.pi * i / N
        shape = [1, 1, 1]
        shape[ax] = -1
        ck = ck / np.sqrt(1 - 2.0 / 3 * np.sin(0.5 * w) ** 2
                          ).reshape(shape)
    p3 = np.abs(ck) ** 2 * L ** 3
    p3[0, 0, 0] = 0.0

    isq = (ix[:, None, None] ** 2 + ix[None, :, None] ** 2
           + iz[None, None, :] ** 2)
    knorm = np.sqrt(isq.astype('f8'))
    mu = np.where(isq == 0, 0.0,
                  iz[None, None, :] / np.where(isq == 0, 1.0, knorm))
    wgt = np.where((iz == 0) | (iz == N // 2), 1.0, 2.0)
    wgt = np.broadcast_to(wgt[None, None, :], p3.shape)

    q = shell_thresholds(N, L)
    nk = len(q) - 1
    kbin = np.searchsorted(q, isq, side='right') - 1
    muedges = np.linspace(-1, 1, Nmu + 1)
    mubin = np.minimum(np.digitize(mu, muedges) - 1, Nmu - 1)
    keep = (kbin < nk).reshape(-1)
    flat = (kbin * Nmu + mubin).reshape(-1)[keep]

    def hist(v):
        return np.bincount(flat, weights=v.reshape(-1)[keep],
                           minlength=nk * Nmu).reshape(nk, Nmu)

    modes = hist(wgt)
    with np.errstate(invalid='ignore', divide='ignore'):
        out = {'modes': modes, 'power': hist(wgt * p3) / modes,
               'modes_1d': modes.sum(axis=1)}
        for ell in poles:
            leg = legval(mu, [0] * ell + [1])
            out['power_%d' % ell] = (2 * ell + 1) * hist(
                wgt * p3 * leg).sum(axis=1) / out['modes_1d']
    return out


def phase_oracle(nmesh=64, boxsize=1000.0, nbar=2e-4, seed=SEED):
    """FFTPower through ``nbodykit_tpu.lab`` on a seeded box against
    :func:`reference_fftpower` on the same particles.

    Nmu=4 here: no lattice mode lies on an interior mu edge (mu = 1/2
    would need kx^2 + ky^2 = 3 kz^2, which has no integer solution;
    mu = 0 is the exact kz = 0 plane), so a mode's bin does not hang
    on how a division rounds and the mode counts compare exactly."""
    from nbodykit_tpu.lab import FFTPower, UniformCatalog
    Nmu, poles = 4, [0, 2, 4]
    cat = UniformCatalog(nbar=nbar, BoxSize=boxsize, seed=seed)
    mesh = cat.to_mesh(Nmesh=nmesh, resampler='cic', compensated=True)
    r = FFTPower(mesh, mode='2d', Nmu=Nmu, poles=poles)
    ref = reference_fftpower(np.asarray(cat['Position']), boxsize,
                             nmesh, Nmu, poles)

    check(np.array_equal(r.power['modes'], ref['modes']),
          'oracle: (k, mu) mode counts differ from the reference')
    check(np.array_equal(r.poles['modes'], ref['modes_1d']),
          'oracle: k mode counts differ from the reference')
    # the k = 0 shell holds the cleared DC mode alone: P = 0 there
    ok = (ref['modes'] > 0) & (ref['power'] > 0)
    err2d = np.abs(r.power['power'].real[ok] / ref['power'][ok] - 1)
    p0 = ref['power_0']
    ok1 = (ref['modes_1d'] > 0) & (p0 > 0)
    check(r.poles['power_0'].real[0] == 0, 'oracle: DC mode not cleared')
    errs = {}
    for ell in poles:
        got = r.poles['power_%d' % ell].real
        errs[ell] = float(np.max(
            np.abs(got[ok1] - ref['power_%d' % ell][ok1]) / p0[ok1]))
    rec = {'nmesh': nmesh, 'npart': int(cat.size),
           'max_rel_err_2d': float(err2d.max()),
           'max_err_poles_over_p0': errs,
           'modes': float(ref['modes'].sum())}
    # f4 mesh, f4 FFT: the repo's f32 target is 1e-4 on well-populated
    # bins (tests/test_f32_accuracy.py); single-mode bins see the raw
    # f4 FFT error, so the bound here is a few times that
    check(err2d.max() < 1e-3, 'oracle: P(k, mu) off by %.3g' % err2d.max())
    check(max(errs.values()) < 1e-3,
          'oracle: multipoles off by %r (in units of P0)' % errs)
    return rec


# ---------------------------------------------------------------------------
# phase: fftpower — the lab call at the deployment's size

def expected_modes(nmesh, boxsize):
    """Modes per k shell of FFTPower's default edges, counted on the
    integer lattice, Hermitian pairs counted twice."""
    N = int(nmesh)
    q = shell_thresholds(N, boxsize)
    nk = len(q) - 1
    ix = np.fft.fftfreq(N, 1.0 / N).astype('i8')
    iz = np.arange(N // 2 + 1)
    wz = np.where((iz == 0) | (iz == N // 2), 1.0, 2.0)
    pl = ix[:, None, None] ** 2 + iz[None, None, :] ** 2
    out = np.zeros(nk)
    for iy in ix:                       # one (N, Nc) plane at a time
        kb = np.searchsorted(q, pl[:, 0, :] + iy * iy, side='right') - 1
        keep = kb < nk
        out += np.bincount(kb[keep],
                           weights=np.broadcast_to(wz, kb.shape)[keep],
                           minlength=nk)
    return out


def run_fftpower(nmesh, boxsize, nbar, seed, Nmu=5, poles=(0, 2, 4)):
    """``UniformCatalog -> to_mesh -> FFTPower`` as a user writes it,
    under whatever ambient mesh the caller set.  Returns the result,
    the wall (the result columns are host arrays, so the call ends
    synchronised; ``block_until_ready`` on the catalog makes the
    position draw part of the wall rather than of the next call)."""
    import jax
    from nbodykit_tpu.lab import FFTPower, UniformCatalog
    t0 = time.perf_counter()
    cat = UniformCatalog(nbar=nbar, BoxSize=boxsize, seed=seed)
    mesh = cat.to_mesh(Nmesh=nmesh, resampler='cic', compensated=True)
    r = FFTPower(mesh, mode='2d', Nmu=Nmu, poles=list(poles))
    jax.block_until_ready(cat['Position'])
    return r, time.perf_counter() - t0, mesh


def check_shotnoise(r, nmesh, min_modes):
    """No NaN where there are modes, the exact mode count, and a flat
    monopole at the shot noise 1/nbar on well-populated shells: each
    within 1% plus five standard deviations of a shell's estimate (a
    shell of M counted modes has M/2 independent ones, so sigma/P =
    sqrt(2/M): 2.4% in all at 1e5 modes), and their mean likewise."""
    modes = np.asarray(r.poles['modes'])
    check(np.array_equal(modes, expected_modes(nmesh, r.attrs['BoxSize'][0])),
          'fftpower: k mode counts differ from the lattice count')
    pw = np.asarray(r.power['power'].real)
    check(np.isfinite(pw[np.asarray(r.power['modes']) > 0]).all(),
          'fftpower: NaN/Inf in P(k, mu) where there are modes')
    for ell in r.attrs['poles']:
        check(np.isfinite(np.asarray(
            r.poles['power_%d' % ell].real)[modes > 0]).all(),
            'fftpower: NaN/Inf in P_%d' % ell)
    shot = float(r.attrs['shotnoise'])
    p0 = np.asarray(r.poles['power_0'].real)
    well = modes >= min_modes
    check(well.sum() >= 3, 'fftpower: no well-populated shells')
    off = np.abs(p0[well] / shot - 1)
    worst = float(off.max())
    mean = float(np.sum(p0[well] * modes[well]) / modes[well].sum()
                 / shot)
    check(np.all(off < 0.01 + 5 * np.sqrt(2 / modes[well])),
          'fftpower: P0 off 1/nbar by up to %.3g on shells with >= %d '
          'modes' % (worst, min_modes))
    check(abs(mean - 1) < 0.01 + 5 * np.sqrt(2 / modes[well].sum()),
          'fftpower: mode-weighted P0 / (1/nbar) = %.4f' % mean)
    return {'shotnoise': shot, 'p0_over_shot_mean': mean,
            'p0_over_shot_worst': worst,
            'modes': float(modes.sum())}


def phase_fftpower(nmesh=ONE_CHIP_NMESH, boxsize=DESI_LIKE['BoxSize'],
                   nbar=DESI_LIKE['nbar'], seed=SEED, min_modes=10000):
    """The lab FFTPower at the deployment's size, twice: cold (with
    every compile) and warm."""
    c0 = compile_seconds()
    r, cold, _ = run_fftpower(nmesh, boxsize, nbar, seed)
    c1 = compile_seconds()
    r2, warm, _ = run_fftpower(nmesh, boxsize, nbar, seed)
    rec = {'nmesh': nmesh, 'npart': int(r.attrs['N1']),
           'cold_wall_s': cold, 'warm_wall_s': warm,
           'compile_s': c1 - c0,
           'warm_compile_s': compile_seconds() - c1,
           'peak_bytes_in_use': peak_bytes()}
    rec.update(check_shotnoise(r, nmesh, min_modes))
    check(np.array_equal(np.asarray(r.power['power']),
                         np.asarray(r2.power['power']), equal_nan=True),
          'fftpower: the same call twice gave two answers')
    return rec


# ---------------------------------------------------------------------------
# phase: serve — three requests through the server

def phase_serve(nmesh=ONE_CHIP_NMESH, npart=10 ** 7, hbm_bytes=None,
                deadline_s=900.0):
    """Three FFTPower requests, submitted and waited in turn, two with
    the same seed."""
    from nbodykit_tpu.diagnostics.metrics import REGISTRY
    from nbodykit_tpu.serve import AnalysisRequest, AnalysisServer
    from nbodykit_tpu.serve.scheduler import BOX_SIZE, program_label

    def misses(label):
        snap = REGISTRY.snapshot().get('compile.%s.misses' % label)
        return int(snap['value']) if snap else 0

    results, walls, miss = [], [], []
    with AnalysisServer(per_task=1, hbm_bytes=hbm_bytes) as server:
        for seed in (7, 7, 8):
            req = AnalysisRequest(algorithm='FFTPower', nmesh=nmesh,
                                  npart=npart, seed=seed,
                                  deadline_s=deadline_s)
            label = program_label(req)
            t0 = time.perf_counter()
            ticket = server.submit(req)
            res = server.wait(ticket, timeout=deadline_s)
            walls.append(time.perf_counter() - t0)
            miss.append(misses(label))
            check(res is not None and res.status == 'completed',
                  'serve: request %d ended %r' % (
                      len(results), res and res.to_dict()))
            results.append(res)
        summary = server.summary()

    a, b, c = (np.asarray(r.y) for r in results)
    check(a.tobytes() == b.tobytes(),
          'serve: the same seed twice gave two spectra')
    check(a.tobytes() != c.tobytes(),
          'serve: another seed gave the same spectrum')
    check(np.isfinite(a).all() and np.isfinite(c).all()
          and a.shape == (nmesh // 2,),
          'serve: spectrum not finite of shape (%d,)' % (nmesh // 2))
    for key in ('lost', 'retried', 'fault_degraded', 'admit_degraded'):
        check(summary[key] == 0,
              'serve: %s = %r' % (key, summary[key]))
    check(summary['completed'] == 3, 'serve: %r' % summary)
    check(miss[0] >= 1 and miss[1] == miss[0] and miss[2] == miss[0],
          'serve: compile misses after each request %r — a same-shape '
          'request must compile nothing' % miss)
    # uniform particles: the served P(k) is the shot noise too
    shot = BOX_SIZE ** 3 / npart
    nm = np.asarray(results[0].nmodes)
    well = nm >= min(10000, nm.max() / 4)
    off = np.abs(a[well] / shot - 1)
    worst = float(off.max())
    check(np.all(off < 0.01 + 5 * np.sqrt(2 / nm[well])),
          'serve: P(k) off 1/nbar by up to %.3g' % worst)
    return {'nmesh': nmesh, 'npart': npart, 'walls_s': walls,
            'compile_misses': miss,
            'p_over_shot_worst': worst,
            'hbm_bytes': hbm_bytes,
            'peak_bytes_in_use': peak_bytes(),
            'summary': {k: summary[k] for k in (
                'submitted', 'completed', 'lost', 'retried',
                'fault_degraded', 'admit_degraded', 'programs')}}


# ---------------------------------------------------------------------------
# phase: multichip (--chips 4 only)

def phase_multichip(mesh1, mesh4, nmesh=ONE_CHIP_NMESH,
                    nmesh_full=DESI_LIKE['Nmesh'],
                    boxsize=DESI_LIKE['BoxSize'],
                    nbar=DESI_LIKE['nbar'], seed=SEED,
                    min_modes=10000):
    """The same seeded catalog on a 1-device mesh and on ``mesh4``:
    equal P(k), the painted field really spread over the devices; then
    the deployment's published mesh on ``mesh4``."""
    from nbodykit_tpu.lab import use_mesh
    ndev = int(mesh4.devices.size)
    with use_mesh(mesh1):
        r1, wall1, _ = run_fftpower(nmesh, boxsize, nbar, seed)
    with use_mesh(mesh4):
        r4, cold4, _ = run_fftpower(nmesh, boxsize, nbar, seed)
        r4, wall4, m4 = run_fftpower(nmesh, boxsize, nbar, seed)
        field = m4.compute(mode='real').value
    shards = field.addressable_shards
    where = sorted(str(s.device) for s in shards)
    sizes = [int(s.data.nbytes) for s in shards]
    check(len(set(where)) == ndev,
          'multichip: field shards sit on %r' % (where,))
    check(all(abs(b * ndev / float(field.nbytes) - 1) < 0.01
              for b in sizes),
          'multichip: shard bytes %r of %d' % (sizes, field.nbytes))
    del field, shards

    check(np.array_equal(r1.power['modes'], r4.power['modes']),
          'multichip: mode counts differ between 1 and %d devices'
          % ndev)
    p1 = np.asarray(r1.power['power'].real)
    p4 = np.asarray(r4.power['power'].real)
    ok = (np.asarray(r1.power['modes']) > 0) & (p1 != 0)    # not DC
    err = float(np.max(np.abs(p4[ok] / p1[ok] - 1)))
    # the device-count-invariance tests hold 1e-8 in f8
    # (tests/test_fftpower.py); in f4 the repo's bound is 1e-4
    # (tests/test_f32_accuracy.py)
    check(err < 1e-4, 'multichip: P(k, mu) differs by %.3g between 1 '
          'and %d devices' % (err, ndev))
    rec = {'ndevices': ndev, 'nmesh': nmesh, 'npart': int(r1.attrs['N1']),
           'max_rel_diff_1_vs_n': err, 'wall_1dev_s': wall1,
           'cold_wall_ndev_s': cold4, 'wall_ndev_s': wall4,
           'shard_devices': where, 'shard_bytes': sizes}
    rec.update(check_shotnoise(r4, nmesh, min_modes))

    with use_mesh(mesh4):       # once: four chips cost four times
        rf, coldf, _ = run_fftpower(nmesh_full, boxsize, nbar, seed)
    full = check_shotnoise(rf, nmesh_full, min_modes)
    rec['full'] = dict(full, nmesh=nmesh_full, cold_wall_s=coldf,
                       peak_bytes_in_use=peak_bytes())
    return rec


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--chips', type=int, default=1, choices=(1, 4),
                    help='4: run only the four-chip path and what it '
                         'is compared with')
    args = ap.parse_args(argv)

    from nbodykit_tpu._jax_compat import enable_compile_cache
    enable_compile_cache()
    dev = device_record()
    if dev['platform'] != 'tpu' or dev['count'] != args.chips:
        print('chip_smoke: needs %d TPU device(s), JAX reports %r'
              % (args.chips, dev), file=sys.stderr)
        return 1

    try:
        if args.chips == 4:
            from nbodykit_tpu.lab import tpu_mesh
            say('sizes', source='desi_like', **DESI_LIKE)
            say('multichip', **phase_multichip(tpu_mesh(1), tpu_mesh()))
        else:
            say('sizes', source='desi_like', note='Nmesh cut from %d '
                'to %d to fit one chip; N=1e7 kept'
                % (DESI_LIKE['Nmesh'], ONE_CHIP_NMESH),
                Nmesh=ONE_CHIP_NMESH, BoxSize=DESI_LIKE['BoxSize'],
                nbar=DESI_LIKE['nbar'])
            device = phase_device()
            say('device', **device)
            say('oracle', **phase_oracle())
            say('fftpower', **phase_fftpower())
            say('serve', **phase_serve(hbm_bytes=device['bytes_limit']))
    except Exception as e:
        import traceback
        traceback.print_exc()
        say('failed', error='%s: %s' % (type(e).__name__, str(e)[:2000]))
        return 1
    print(json.dumps({'ok': True, 'device': dev}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
