"""Tests for nbodykit_tpu.forward: the differentiable forward model
(docs/FORWARD.md).

Finite-difference gradient checks for every adjoint in the pipeline —
paint (each kernel's contract), readout, the Poisson force, and the
full LPT+KDK+paint map on the 8-device mesh.  All FD probes run f8
with eps=1e-6: the CIC window is continuous but kinked, so larger eps
sits on the kink noise (1-10% apparent error for a CORRECT gradient)
while 1e-6 converges to ~1e-7 relative.  Multi-device pipelines are
always jitted — eager shard_map re-traces per call and is pathological.

Plus: 2LPT-vs-Zel'dovich displacement asymptotics, bit-identical
forward replay, field-level recovery beating the FFTRecon baseline
(the 128^3 toy is slow-tier), and the serve plane's Forward request
paths (validate / admit / degrade / reject / end-to-end with shadow
verification).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from nbodykit_tpu.forward import (ForwardModel, fftrecon_baseline,
                                  linear_init, lpt_init, make_loss,
                                  make_paint, mean_cross_correlation,
                                  normalized_amplitude, recover)
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
from nbodykit_tpu.pmesh import ParticleMesh, memory_plan

requires_x64 = pytest.mark.skipif(
    not jax.config.jax_enable_x64,
    reason="finite-difference gradient checks need f8")


def _fd_vs_grad(loss, x, d, eps=1e-6):
    """(central finite difference, <grad, d>) along unit direction d."""
    d = d / jnp.sqrt(jnp.sum(d * d))
    g = jax.grad(loss)(x)
    fd = (float(loss(x + eps * d)) - float(loss(x - eps * d))) \
        / (2.0 * eps)
    return fd, float(jnp.sum(g * d))


def _assert_close(fd, dot, rtol):
    assert abs(fd - dot) <= rtol * max(abs(fd), abs(dot), 1e-10), \
        "FD %r vs grad %r (rel %.3g)" % (
            fd, dot, abs(fd - dot) / max(abs(fd), 1e-300))


# ---------------------------------------------------------------------------
# per-kernel paint adjoints (single device, eager — small and exact)

@requires_x64
@pytest.mark.parametrize('method',
                         ['scatter', 'sort', 'segsum', 'streams'])
def test_paint_adjoint_matches_fd(method):
    pm = ParticleMesh(Nmesh=8, BoxSize=100.0, dtype='f8')
    npart = 64
    paint, cfg = make_paint(pm, npart, 'cic', method=method)
    assert cfg['adjoint_mode'] == (
        'native' if method == 'scatter' else 'custom_vjp')
    rng = np.random.RandomState(42)
    pos = jnp.asarray(rng.uniform(0.0, 100.0, (npart, 3)))
    mass = jnp.asarray(1.0 + 0.5 * rng.random_sample(npart))
    tgt = jnp.asarray(rng.normal(size=pm.shape_real))

    fd, dot = _fd_vs_grad(
        lambda p: jnp.sum(tgt * paint(p, mass)), pos,
        jnp.asarray(rng.normal(size=(npart, 3))))
    _assert_close(fd, dot, 1e-5)
    fd, dot = _fd_vs_grad(
        lambda m: jnp.sum(tgt * paint(pos, m)), mass,
        jnp.asarray(rng.normal(size=npart)))
    _assert_close(fd, dot, 1e-5)


def test_make_paint_refuses_mxu_pin():
    pm = ParticleMesh(Nmesh=8, BoxSize=100.0, dtype='f8')
    with pytest.raises(ValueError, match='adjoint contract'):
        make_paint(pm, 64, 'cic', method='mxu')


def test_resolve_forward_paint_demotes_what_has_no_adjoint():
    """The grad-mode demotion lives with the forward model: an option
    naming 'mxu' runs 'scatter', loudly; 'sort' keeps its kernel under
    the custom_vjp; admission prices the same answer."""
    import nbodykit_tpu
    from nbodykit_tpu.diagnostics import REGISTRY
    from nbodykit_tpu.forward.adjoint import (grad_paint_method,
                                              resolve_forward_paint)

    def fallbacks():
        snap = REGISTRY.snapshot().get('forward.grad_fallback')
        return snap['value'] if snap else 0
    n0 = fallbacks()
    with nbodykit_tpu.set_options(paint_method='mxu'):
        cfg, mode = resolve_forward_paint()
    assert (cfg['paint_method'], mode) == ('scatter', 'native')
    assert (cfg['source'], cfg['winner_name']) == ('grad-fallback', 'mxu')
    assert fallbacks() == n0 + 1
    with nbodykit_tpu.set_options(paint_method='sort'):
        cfg, mode = resolve_forward_paint()
    assert (cfg['paint_method'], mode, cfg['source']) == \
        ('sort', 'custom_vjp', 'explicit')
    assert fallbacks() == n0 + 1
    assert [grad_paint_method(m) for m in
            ('scatter', 'streams', 'mxu')] == ['scatter', 'streams',
                                               'scatter']


@requires_x64
def test_readout_gradient_matches_fd():
    pm = ParticleMesh(Nmesh=8, BoxSize=100.0, dtype='f8')
    rng = np.random.RandomState(1)
    field = jnp.asarray(rng.normal(size=pm.shape_real))
    pos = jnp.asarray(rng.uniform(0.0, 100.0, (32, 3)))
    fd, dot = _fd_vs_grad(
        lambda p: jnp.sum(pm.readout(field, p) ** 2), pos,
        jnp.asarray(rng.normal(size=(32, 3))))
    _assert_close(fd, dot, 1e-5)


@requires_x64
def test_poisson_force_gradient_matches_fd():
    """paint -> k-space Poisson solve -> force readout, as one map."""
    model = ForwardModel(8, 64, BoxSize=100.0, pm_steps=1, dtype='f8')
    rng = np.random.RandomState(2)
    pos = jnp.asarray(rng.uniform(0.0, 100.0, (64, 3)))
    cot = jnp.asarray(rng.normal(size=(64, 3)))
    fd, dot = _fd_vs_grad(
        lambda p: jnp.sum(cot * model.gravity(p)), pos,
        jnp.asarray(rng.normal(size=(64, 3))))
    _assert_close(fd, dot, 1e-4)


# ---------------------------------------------------------------------------
# the full pipeline on the 8-device mesh (jitted; slow tier)

@requires_x64
def test_kdk_gradient_matches_fd_multi(cpu8):
    with use_mesh(cpu8):
        model = ForwardModel(16, 512, BoxSize=100.0, pm_steps=1,
                             dtype='f8')
        obs = jax.jit(model.density)(model.linear_modes(1))
        loss = make_loss(model, obs, noise_std=0.5)
        jloss = jax.jit(loss)
        w = model.lattice.c2r(
            model.lattice.generate_whitenoise(3)) * 0.2
        d = model.lattice.c2r(model.lattice.generate_whitenoise(5))
        d = d / jnp.sqrt(jnp.sum(d * d))
        g = jax.jit(jax.grad(loss))(w)
        eps = 1e-6
        fd = (float(jloss(w + eps * d)) - float(jloss(w - eps * d))) \
            / (2.0 * eps)
        dot = float(jnp.sum(g * d))
    _assert_close(fd, dot, 1e-4)


# ---------------------------------------------------------------------------
# LPT asymptotics + replay determinism

@requires_x64
def test_2lpt_correction_scales_linearly_vs_za():
    """The 2LPT term enters positions as D2 = -(3/7) a^2 against the
    Zel'dovich D1 = a, so rms(x_2lpt - x_za) / rms(x_za - q) must
    scale exactly linearly in a — and the momentum assembly must carry
    the matching -(6/7) a factor."""
    pm = ParticleMesh(Nmesh=16, BoxSize=100.0, dtype='f8')
    modes = pm.generate_whitenoise(7) * normalized_amplitude(
        pm, -2.5, 0.05)
    q = pm.generate_uniform_particle_grid(
        shift=0.0, dtype=pm.compute_dtype)

    def ratio(a):
        x1, p1 = lpt_init(pm, modes, a=a, order=1)
        x2, p2 = lpt_init(pm, modes, a=a, order=2)
        num = float(jnp.sqrt(jnp.mean((x2 - x1) ** 2)))
        den = float(jnp.sqrt(jnp.mean((x1 - q) ** 2)))
        assert num > 0      # 2LPT source must be nonzero
        # momentum: mom2 - mom1 = a^{3/2} (-6/7) a psi2
        #           pos2 - pos1 = (-3/7) a^2 psi2
        dp = float(jnp.sqrt(jnp.mean((p2 - p1) ** 2)))
        dx = float(jnp.sqrt(jnp.mean((x2 - x1) ** 2)))
        assert dp == pytest.approx(2.0 * a ** 0.5 * dx, rel=1e-10)
        return num / den

    r1, r2 = ratio(0.05), ratio(0.1)
    assert r2 / r1 == pytest.approx(2.0, rel=1e-10)


def test_growth_table_pins_lcdm_d1_and_eds_limit():
    """GrowthTable in the stepper's early-time gauge (D1 -> a):
    Omega_m=0.3 pays the textbook Lambda growth suppression at a=1,
    and Omega_m=1 reproduces the EdS closed forms."""
    from nbodykit_tpu.forward import GrowthTable, dkick, ddrift
    g = GrowthTable(0.3)
    assert g.D1(1.0) == pytest.approx(0.7789, abs=2e-3)
    assert 0.4 < g.f1(1.0) < 0.6          # ~ Omega_m(a=1)^0.55
    assert g.D2(1.0) < 0                  # EdS-sign convention
    e = GrowthTable(1.0)
    for a in (0.1, 0.33, 0.77, 1.0):
        assert e.D1(a) == pytest.approx(a, rel=1e-6)
        assert e.f1(a) == pytest.approx(1.0, abs=1e-5)
        assert e.D2(a) == pytest.approx(-3.0 / 7 * a * a, rel=1e-4)
        assert e.f2(a) == pytest.approx(2.0, abs=1e-4)
    for a0, a1 in ((0.1, 0.4), (0.5, 1.0)):
        assert e.dkick(a0, a1) == pytest.approx(dkick(a0, a1),
                                                rel=1e-12)
        assert e.ddrift(a0, a1) == pytest.approx(ddrift(a0, a1),
                                                 rel=1e-12)


@requires_x64
def test_lcdm_stepper_suppresses_growth_like_the_table():
    """Evolving the same tiny ZA displacement through the EdS and
    Omega_m=0.3 steppers: the ratio of the two growth factors must
    match D1_lcdm/D1_eds from the table (the mesh's CIC force
    softening cancels in the ratio to first order)."""
    def growth_ratio(omega_m):
        m = ForwardModel(8, pm_steps=8, order=1, omega_m=omega_m,
                         delta_rms=1e-4, dtype='f8', a_start=0.1)
        modes = m.linear_modes(3)
        pos0, _ = lpt_init(m.lattice, modes, a=0.1, order=1,
                           growth=m.growth)
        q = m.lattice.generate_uniform_particle_grid(shift=0.0)
        pos1, _ = m.evolve(modes)
        d0, d1 = np.asarray(pos0 - q), np.asarray(pos1 - q)
        return float(np.sum(d0 * d1) / np.sum(d0 * d0))

    from nbodykit_tpu.forward import GrowthTable
    g = GrowthTable(0.3)
    want = (g.D1(1.0) / g.D1(0.1)) / (1.0 / 0.1)
    got = growth_ratio(0.3) / growth_ratio(1.0)
    assert got == pytest.approx(want, rel=0.05)


def test_forward_replay_bit_identical():
    """Same modes -> same density, bit for bit (the contract shadow
    verification and result memoization stand on)."""
    model = ForwardModel(8, 64, BoxSize=100.0, pm_steps=2, dtype='f8')
    modes = model.linear_modes(9)
    dens = jax.jit(model.density)
    a = np.asarray(dens(modes))
    b = np.asarray(dens(modes))
    assert np.array_equal(a, b)
    # and through a fresh identically-configured model
    model2 = ForwardModel(8, 64, BoxSize=100.0, pm_steps=2, dtype='f8')
    c = np.asarray(jax.jit(model2.density)(model2.linear_modes(9)))
    assert np.array_equal(a, c)


# ---------------------------------------------------------------------------
# field-level recovery vs the classical baseline

@requires_x64
def test_recovery_beats_fftrecon_small():
    """32^3: linear-init Adam recovery of the initial field must beat
    FFTRecon (LGS) on whole-field cross-correlation with the truth."""
    model = ForwardModel(32, 32 ** 3, BoxSize=1000.0, pm_steps=2,
                         dtype='f8')
    truth = model.linear_modes(0)
    obs = jax.jit(model.density)(truth)
    w, losses = recover(model, obs, steps=80, lr=0.1, noise_std=0.1,
                        white0=linear_init(model, obs))
    assert losses[-1] < losses[0]
    lat = model.lattice
    r_rec = float(mean_cross_correlation(
        lat, model.modes_from_white(w), truth))
    pos, _ = model.evolve(truth)
    base = fftrecon_baseline(model, pos)
    r_base = float(mean_cross_correlation(lat, base, truth))
    assert r_rec > r_base, \
        "recovered r=%.4f does not beat FFTRecon r=%.4f" % (r_rec,
                                                            r_base)


@requires_x64
def test_recovery_beats_fftrecon_128():
    """The 128^3 toy, slow tier: same contract at production mesh
    resolution.  delta_rms scales the displacement regime of the 32^3
    toy (~1.8 cells rms) onto the bigger mesh — at delta_rms=1 the
    128^3 field moves ~5 cells and no plain gradient optimizer
    converges (docs/FORWARD.md 'Displacement per cell governs
    convergence')."""
    model = ForwardModel(128, 128 ** 3, BoxSize=1000.0, pm_steps=2,
                         delta_rms=0.36, dtype='f8')
    truth = model.linear_modes(0)
    obs = jax.jit(model.density)(truth)
    # lr shrinks with the mesh (0.1 at 32^3, 0.02 at 64^3): constant-
    # magnitude Adam steps inject white noise at every scale, and the
    # stable size falls as the k range grows
    w, losses = recover(model, obs, steps=40, lr=0.01, noise_std=0.1,
                        white0=linear_init(model, obs))
    assert losses[-1] < losses[0]
    lat = model.lattice
    r_rec = float(mean_cross_correlation(
        lat, model.modes_from_white(w), truth))
    pos, _ = model.evolve(truth)
    base = fftrecon_baseline(model, pos)
    r_base = float(mean_cross_correlation(lat, base, truth))
    assert r_rec > r_base, \
        "recovered r=%.4f does not beat FFTRecon r=%.4f" % (r_rec,
                                                            r_base)


def test_linear_init_requires_matching_meshes():
    model = ForwardModel(16, 8 ** 3, BoxSize=100.0, dtype='f8')
    with pytest.raises(ValueError, match='nmesh'):
        linear_init(model, jnp.ones(model.pm.shape_real))


# ---------------------------------------------------------------------------
# the serve plane: Forward as traffic

def test_forward_request_validation_and_program_key():
    from nbodykit_tpu.serve import AnalysisRequest
    r = AnalysisRequest(algorithm='Forward', nmesh=16, npart=4096,
                        pm_steps=2)
    assert r.pm_steps == 2
    assert r.program_key(1)[-1] == 2       # step count is program id
    r5 = AnalysisRequest(algorithm='Forward', nmesh=16, npart=4096)
    assert r5.pm_steps == 5                # default schedule
    assert r.program_key(1) != r5.program_key(1)
    with pytest.raises(ValueError, match='cube'):
        AnalysisRequest(algorithm='Forward', nmesh=16, npart=5000)
    with pytest.raises(ValueError, match='pm_steps'):
        AnalysisRequest(algorithm='FFTPower', nmesh=16, npart=4096,
                        pm_steps=3)
    with pytest.raises(ValueError, match='FFTPower only'):
        AnalysisRequest(algorithm='Forward', nmesh=16, npart=4096,
                        data_ref={'path': 'x', 'format': 'binary'})


def test_forward_admission_admit_degrade_reject():
    from nbodykit_tpu.serve import (ADMIT, DEGRADE, REJECT,
                                    AnalysisRequest, admit)
    # admit: small shape, priced with the reverse-pass branch
    d = admit(AnalysisRequest(algorithm='Forward', nmesh=16,
                              npart=8 ** 3, pm_steps=2), ndevices=1,
              hbm_bytes=16e9)
    assert d.status == ADMIT
    assert d.plan['workload'] == 'forward'
    assert d.plan['grad_residual_bytes'] > 0
    # degrade: 464^3 particles at nmesh=64 peak ~8.27 GB unchunked,
    # ~7.74 GB at paint_chunk 8M — a budget between the two admits
    # degraded through the scoped ladder
    d = admit(AnalysisRequest(algorithm='Forward', nmesh=64,
                              npart=464 ** 3, pm_steps=2,
                              paint_method='scatter'), ndevices=1,
              hbm_bytes=9.3e9)
    assert d.status == DEGRADE
    assert d.options.get('paint_chunk_size')
    assert [r[0] for r in d.rungs][-1] == 'paint_chunk_size/2'
    # reject over budget, structured
    d = admit(AnalysisRequest(algorithm='Forward', nmesh=64,
                              npart=464 ** 3, pm_steps=2,
                              paint_method='scatter'), ndevices=1,
              hbm_bytes=4e9)
    assert d.status == REJECT
    assert d.reason['code'] == 'over_budget'
    # reject indivisible particle lattice: ng=12 on 8 devices
    d = admit(AnalysisRequest(algorithm='Forward', nmesh=16,
                              npart=12 ** 3, pm_steps=2), ndevices=8,
              hbm_bytes=16e9)
    assert d.status == REJECT
    assert d.reason['code'] == 'indivisible'
    assert 'lattice' in d.reason['detail']


def test_forward_memory_plan_prices_reverse_pass():
    fwd = memory_plan(64, 32 ** 3, ndevices=1, dtype='f4',
                      workload='forward', pm_steps=5)
    base = memory_plan(64, 32 ** 3, ndevices=1, dtype='f4')
    assert fwd['workload'] == 'forward'
    assert fwd['pm_steps'] == 5
    assert fwd['grad_residual_bytes'] > 0
    assert fwd['peak_bytes'] > base['peak_bytes']
    # residuals grow with the step count
    deeper = memory_plan(64, 32 ** 3, ndevices=1, dtype='f4',
                         workload='forward', pm_steps=10)
    assert deeper['peak_bytes'] > fwd['peak_bytes']


def test_forward_served_end_to_end_with_shadow_verify():
    """A Forward request through the live server: admitted with the
    reverse-pass plan, completed, 0 lost — and when verify=True the
    shadow re-execution on a different sub-mesh agrees bit-identically
    (the counters, not faith, say so)."""
    from nbodykit_tpu.serve import (AnalysisRequest, AnalysisServer,
                                    BatchPolicy)
    with AnalysisServer(per_task=4,
                        batch=BatchPolicy(max_delay_s=0)) as srv:
        assert len(srv.meshes) >= 2, 'shadow needs two sub-meshes'
        res = srv.wait(srv.submit(AnalysisRequest(
            algorithm='Forward', nmesh=16, npart=8 ** 3, pm_steps=1,
            seed=3, deadline_s=600.0, verify=True)), timeout=600)
        summary = srv.summary()
    assert res.status == 'completed'
    assert summary['lost'] == 0
    assert summary['shadow_verified'] == 1
    assert summary['shadow_mismatch'] == 0
    y = np.asarray(res.y, dtype=np.float64)
    assert np.isfinite(y).all() and (np.abs(y) > 0).any()
