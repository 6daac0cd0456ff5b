"""Symplectic kick-drift-kick PM stepper — a pure, differentiable
function of the linear modes.

Gauge and units (Einstein-de-Sitter, Omega_m = 1, H0 = 1, positions in
box units): with canonical momentum p = a^2 dx/dt the equations of
motion separate into

  dx/da = p * a^{-3/2}           (drift)
  dp/da = F(x) * a^{-1/2}        (kick)

where F is the PM force, F_i(k) = 1.5 Omega_m * i k_i / k^2 * delta_k
read out at the particle positions.  The second-order KDK integrator
uses the EXACT time integrals of the prefactors over each interval
(Quinn et al. 1997 convention):

  dkick(a0, a1)  = int a^{-1/2} da = 2 (sqrt(a1) - sqrt(a0))
  ddrift(a0, a1) = int a^{-3/2} da = 2 (1/sqrt(a0) - 1/sqrt(a1))

so the Zel'dovich flow x = q + a psi, p = a^{3/2} psi (lpt.py) is an
exact solution of the discrete operators at linear order up to the
O(da^3) midpoint error — the property the 2LPT-vs-ZA asymptotics test
leans on.

The same equations hold for a general matter + Lambda background with
``E(a) = H(a)/H0``: the prefactor integrals become

  dkick(a0, a1)  = int da / (a^2 E(a))
  ddrift(a0, a1) = int da / (a^3 E(a))

(EdS ``E = a^{-3/2}`` recovers the closed forms above) and the LPT
initial conditions use the tabulated growth factors D1(a)/D2(a) and
rates f1/f2 from the :mod:`..cosmology.background` ODE solver instead
of the EdS ``D1 = a``, ``D2 = -(3/7) a^2``.  :class:`GrowthTable`
packages exactly that — solved once at model build, interpolated on a
host-side table, so the traced program still sees static per-step
prefactors.  ``ForwardModel(omega_m=1)`` (the default) keeps the EdS
closed forms bit-for-bit.

``ForwardModel`` packages lattice + force mesh + grad-safe paint
(adjoint.make_paint) into the modes -> density map the serve plane
runs as a ``Forward`` request; ``jax.grad`` through
``ForwardModel.density`` is the backward pass every field-level
inference sample pays, priced by ``pmesh.memory_plan(
workload='forward', pm_steps=...)``.
"""

import numpy as np
import jax.numpy as jnp

from ..pmesh import ParticleMesh
from .lpt import _k_inv_k2, lpt_init, linear_amplitude, modes_from_white
from .adjoint import make_paint


def dkick(a0, a1):
    """Exact kick prefactor integral int_{a0}^{a1} a^{-1/2} da (EdS)."""
    return 2.0 * (np.sqrt(a1) - np.sqrt(a0))


def ddrift(a0, a1):
    """Exact drift prefactor integral int_{a0}^{a1} a^{-3/2} da (EdS)."""
    return 2.0 * (1.0 / np.sqrt(a0) - 1.0 / np.sqrt(a1))


# Gauss-Legendre nodes for the LCDM prefactor integrals: the
# integrands 1/(a^2 E) and 1/(a^3 E) are smooth on any step interval,
# so 64 points are exact to machine precision
_GL_X, _GL_W = np.polynomial.legendre.leggauss(64)


class GrowthTable:
    """Tabulated LCDM growth for the forward stepper.

    Solves the first- and second-order growth ODEs once
    (:class:`~nbodykit_tpu.cosmology.background.MatterDominated`,
    matter + Lambda + curvature, radiation ignored) and rescales the
    solution to the *early-time gauge* the stepper and LPT use:
    ``D1(a) -> a`` as ``a -> 0`` (so EdS reduces to ``D1 = a``,
    ``D2 = -(3/7) a^2`` identically, and ``D1(a=1) ~= 0.779`` for
    ``Omega0_m = 0.3`` — the growth suppression a Lambda background
    pays relative to EdS).

    All evaluations are host-side floats interpolated in ``log a`` on
    a dense table — the KDK schedule is static under jit, so per-step
    growth factors enter the traced program as constants, exactly like
    the EdS closed forms they generalize.
    """

    def __init__(self, omega_m, omega_k=0.0, na=8192):
        from ..cosmology.background import MatterDominated
        self.omega_m = float(omega_m)
        self.omega_k = float(omega_k)
        P = MatterDominated(self.omega_m, Omega0_k=self.omega_k)
        # the solver normalizes D1(a_normalize=1) = 1; undo it via the
        # early-time limit D1_raw(a) -> a (Lambda is negligible at
        # a = 1e-4 to ~1e-12), restoring the stepper's gauge
        a_ref = 1e-4
        scale = a_ref / float(P.D1(a_ref))
        self._P = P
        self._lna = np.log(np.geomspace(1e-3, 1.5, int(na)))
        a = np.exp(self._lna)
        self._D1 = np.asarray(P.D1(a), dtype='f8') * scale
        self._f1 = np.asarray(P.f1(a), dtype='f8')
        self._D2 = np.asarray(P.D2(a), dtype='f8') * scale ** 2
        self._f2 = np.asarray(P.f2(a), dtype='f8')

    def _interp(self, tab, a):
        out = np.interp(np.log(np.asarray(a, dtype='f8')),
                        self._lna, tab)
        return float(out) if np.ndim(a) == 0 else out

    def D1(self, a):
        """First-order growth factor (early-time gauge D1 -> a)."""
        return self._interp(self._D1, a)

    def f1(self, a):
        """First-order growth rate dlnD1/dlna."""
        return self._interp(self._f1, a)

    def D2(self, a):
        """Second-order growth factor (EdS limit -(3/7) a^2)."""
        return self._interp(self._D2, a)

    def f2(self, a):
        """Second-order growth rate dlnD2/dlna."""
        return self._interp(self._f2, a)

    def E(self, a):
        """Dimensionless Hubble rate H(a)/H0 (closed form)."""
        out = self._P.efunc(a)
        return float(out) if np.ndim(a) == 0 else out

    def _quad(self, f, a0, a1):
        mid, half = 0.5 * (a0 + a1), 0.5 * (a1 - a0)
        a = mid + half * _GL_X
        return float(np.sum(_GL_W * f(a)) * half)

    def dkick(self, a0, a1):
        """Kick prefactor integral int_{a0}^{a1} da / (a^2 E(a))."""
        return self._quad(lambda a: 1.0 / (a * a * self.E(a)), a0, a1)

    def ddrift(self, a0, a1):
        """Drift prefactor integral int_{a0}^{a1} da / (a^3 E(a))."""
        return self._quad(lambda a: 1.0 / (a ** 3 * self.E(a)),
                          a0, a1)


def power_law(A=1.0, n=-2.5):
    """A pure power-law linear spectrum P(k) = A k^n (box units)."""
    def P(k):
        return A * k ** n
    return P


def normalized_amplitude(pm, n=-2.5, delta_rms=1.0):
    """:func:`~.lpt.linear_amplitude` for a power-law spectrum,
    rescaled so the linear field at a=1 has real-space rms
    ``delta_rms`` on this mesh.

    The variance implied by an amplitude field is the hermitian-
    weighted sum of amp^2 over the compressed modes (forward-normalized
    convention: Var[delta(x)] = sum_k P(k)/V), computed exactly here so
    tests and serve get a box- and mesh-independent normalization.
    """
    amp = linear_amplitude(pm, power_law(1.0, n))
    w = jnp.full(pm.shape_complex, 2.0, amp.dtype)
    w = w.at[..., 0].set(1.0)
    if int(pm.Nmesh[2]) % 2 == 0:
        w = w.at[..., -1].set(1.0)
    var = jnp.sum(w * amp * amp)
    return amp * (delta_rms / jnp.sqrt(var))


class ForwardModel:
    """LPT ICs + KDK PM evolution + paint, as one differentiable map.

    Parameters
    ----------
    nmesh : force/analysis mesh cells per side
    npart : total particles; must be a cube ng^3 with ng divisible by
        the device count (defaults to nmesh^3, one per force-mesh cell)
    pm_steps : number of KDK steps from ``a_start`` to ``a_end``
    order : 1 (Zel'dovich) or 2 (2LPT) initial conditions
    linear_power : P(k) callable; default is a power-law spectrum
        normalized to ``delta_rms`` via :func:`normalized_amplitude`
    dtype : mesh dtype ('f8' for gradient-check work, 'f4' for serve)

    The model owns two meshes: ``lattice`` (ng^3, where the linear
    modes and the inference parametrization live) and ``pm`` (nmesh^3,
    where forces are solved and the observed density is painted).  All
    public maps (:meth:`evolve`, :meth:`density`) are pure functions of
    the modes — jit/grad/shard_map composable, bit-identically
    replayable.
    """

    def __init__(self, nmesh, npart=None, BoxSize=1000.0, pm_steps=5,
                 a_start=0.1, a_end=1.0, order=2, resampler='cic',
                 linear_power=None, spectral_index=-2.5, delta_rms=1.0,
                 omega_m=1.0, dtype='f8', comm=None):
        if npart is None:
            npart = int(nmesh) ** 3
        ng = int(round(float(npart) ** (1.0 / 3.0)))
        if ng ** 3 != int(npart):
            raise ValueError("npart=%d is not a cube; the particle "
                             "lattice needs ng^3" % npart)
        if int(pm_steps) < 1:
            raise ValueError("pm_steps must be >= 1")
        self.pm = ParticleMesh(nmesh, BoxSize, dtype, comm)
        self.lattice = self.pm if ng == int(self.pm.Nmesh[0]) \
            else ParticleMesh(ng, BoxSize, dtype, self.pm.comm)
        self.npart = int(npart)
        self.pm_steps = int(pm_steps)
        self.a_start = float(a_start)
        self.a_end = float(a_end)
        self.order = int(order)
        self.resampler = resampler
        self.omega_m = float(omega_m)
        # omega_m != 1 switches the stepper to the tabulated LCDM
        # growth gauge; the default EdS path keeps the closed-form
        # prefactors bit-for-bit
        self.growth = None if self.omega_m == 1.0 \
            else GrowthTable(self.omega_m)
        self.paint_fn, self.paint_cfg = make_paint(
            self.pm, self.npart, resampler)
        if linear_power is not None:
            self.amp = linear_amplitude(self.lattice, linear_power)
        else:
            self.amp = normalized_amplitude(
                self.lattice, spectral_index, delta_rms)

    # -- parametrizations -------------------------------------------------

    def linear_modes(self, seed):
        """Truth linear modes for ``seed`` (device-count invariant)."""
        return self.lattice.generate_whitenoise(seed) * self.amp

    def white_guess(self):
        """The zero-initialized real whitenoise leaf for inference."""
        return jnp.zeros(self.lattice.shape_real,
                         jnp.dtype(self.lattice.compute_dtype))

    def modes_from_white(self, white):
        """Differentiable real-leaf -> linear-modes map (lpt.py)."""
        return modes_from_white(self.lattice, white, self.amp)

    # -- dynamics ---------------------------------------------------------

    def gravity(self, pos):
        """PM force at ``pos``: paint -> k-space Poisson -> readout x3.
        Returns (npart, 3) box-unit accelerations (the dkick integral
        supplies the remaining a-dependence)."""
        pm = self.pm
        cdt = jnp.dtype(pm.compute_dtype)
        rho = self.paint_fn(pos)
        nbar = self.npart / pm.Ntot
        delta_k = pm.r2c(rho.astype(cdt) / nbar - 1.0)
        kv, inv = _k_inv_k2(pm)
        acc = [pm.readout(
            pm.c2r(1.5 * self.omega_m * 1j * kv[d] * inv * delta_k),
            pos, resampler=self.resampler) for d in range(3)]
        return jnp.stack(acc, axis=-1)

    def _dkick(self, a0, a1):
        return dkick(a0, a1) if self.growth is None \
            else self.growth.dkick(a0, a1)

    def _ddrift(self, a0, a1):
        return ddrift(a0, a1) if self.growth is None \
            else self.growth.ddrift(a0, a1)

    def kdk_step(self, pos, mom, a0, a1):
        """One kick-drift-kick step from a0 to a1 (geometric midpoint
        for the kick split, matching the exact-integral prefactors)."""
        ah = np.sqrt(a0 * a1)
        mom = mom + self.gravity(pos) * self._dkick(a0, ah)
        pos = pos + mom * self._ddrift(a0, a1)
        mom = mom + self.gravity(pos) * self._dkick(ah, a1)
        return pos, mom

    def evolve(self, modes):
        """Evolve linear modes to (positions, momenta) at ``a_end``:
        LPT ICs at ``a_start`` then ``pm_steps`` KDK steps.  Pure in
        ``modes``; the step schedule is static (unrolled under jit)."""
        pos, mom = lpt_init(self.lattice, modes, a=self.a_start,
                            order=self.order, growth=self.growth)
        aa = np.linspace(self.a_start, self.a_end, self.pm_steps + 1)
        for a0, a1 in zip(aa[:-1], aa[1:]):
            pos, mom = self.kdk_step(pos, mom, float(a0), float(a1))
        return pos, mom

    def density(self, modes):
        """The observable: evolved particles painted on the force mesh,
        normalized to 1 + delta.  jax.grad of a scalar of this output
        with respect to the modes (or the white leaf upstream) is the
        field-level inference backward pass."""
        pos, _ = self.evolve(modes)
        rho = self.paint_fn(pos)
        return rho.astype(jnp.dtype(self.pm.compute_dtype)) \
            * (self.pm.Ntot / self.npart)
