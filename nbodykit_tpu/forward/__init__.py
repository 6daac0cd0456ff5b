"""Differentiable forward model: LPT initial conditions, a symplectic
PM stepper, and field-level inference — ROADMAP item 3.

Everything here is a pure function of the linear modes, built from ops
the analysis plane already trusts (paint/readout, dist_rfftn, the
Poisson-solve kernels), so ``jax.grad`` flows through the whole
pipeline.  Layering:

  lpt.py      Zel'dovich + 2LPT displacements from the mockmaker linear
              field, via spectral gradient-of-inverse-Laplacian.
  adjoint.py  grad-safe paint: native reverse mode where the paint
              kernel supports it, an analytic ``jax.custom_vjp``
              (scatter's adjoint IS readout) where it does not.
  pm.py       kick-drift-kick PM stepper; ``ForwardModel`` is the
              modes -> density map the serve plane runs as traffic.
  infer.py    Gaussian field-level posterior + gradient-descent
              recovery of the initial field, FFTRecon as baseline.

See docs/FORWARD.md for the stepper math and the adjoint contract.
"""

from .lpt import (linear_amplitude, linear_modes, modes_from_white,
                  lpt_displacements, lpt_init)
from .adjoint import resolve_forward_paint, make_paint
from .pm import (ForwardModel, GrowthTable, dkick, ddrift,
                 power_law, normalized_amplitude)
from .infer import (binned_power, cross_correlation,
                    mean_cross_correlation, make_loss, linear_init,
                    recover, fftrecon_baseline)

__all__ = [
    'linear_amplitude', 'linear_modes', 'modes_from_white',
    'lpt_displacements', 'lpt_init',
    'resolve_forward_paint', 'make_paint',
    'ForwardModel', 'GrowthTable', 'dkick', 'ddrift', 'power_law',
    'normalized_amplitude',
    'binned_power', 'cross_correlation', 'mean_cross_correlation',
    'make_loss', 'linear_init', 'recover', 'fftrecon_baseline',
]
