"""Grad-safe paint: the adjoint contract per paint kernel.

The compensated paint/readout pair is an adjoint pair — the VJP of
scatter-add IS readout — so the backward pass of painting needs no new
kernels.  What differs per paint method is whether JAX's native
reverse mode can trace the FORWARD:

  scatter          natively differentiable (.at[].add has a transpose
                   rule; the halo exchange is psum/ppermute, also
                   transposable).  Used as-is.
  sort / segsum /  forward is fine under jit but reverse mode either
  streams          fails to trace (sort's while_loop) or materializes
                   absurd residuals.  Wrapped in ``jax.custom_vjp``:
                   that kernel forward, analytic readout backward.
  mxu              its traced overflow contract requires
                   return_dropped, which cannot live inside a silent
                   custom_vjp forward — demoted to 'scatter' by
                   :func:`resolve_forward_paint` (source tag
                   'grad-fallback', counter ``forward.grad_fallback``).

The analytic backward, for out = paint(pos, mass) and cotangent g:

  d/dmass  = readout(g, pos)                       (the classic adjoint)
  d/dpos_d = mass * readout(g, pos, grad_axis=d) * Nmesh_d / Box_d

where ``grad_axis`` readout uses the derivative window dW/dx (cell
units, ops/window.py window_weights_grad), hence the Nmesh/Box factor
to return box-unit gradients.  window_weights_grad matches the a.e.
derivative of the native path, so both modes agree wherever defined —
asserted against finite differences in tests/test_forward.py.
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp

from .. import _global_options, option_scope
from ..diagnostics import counter

# paint kernels jax reverse mode differentiates natively: the scatter
# chain is pure .at[].add / gather jnp ops whose VJP is the existing
# readout
DIFFERENTIABLE_PAINT = frozenset({'scatter'})
# forward fine under jit, reverse mode not: wrapped by make_paint in a
# custom_vjp pair (that kernel forward, readout-based backward)
GRAD_WRAPPED_PAINT = frozenset({'sort', 'segsum', 'streams'})


def grad_paint_method(method):
    """The paint kernel a reverse-mode call runs when the options name
    ``method``: itself where it has an adjoint story, else 'scatter'
    (what admission prices for a ``Forward`` request)."""
    if method in DIFFERENTIABLE_PAINT or method in GRAD_WRAPPED_PAINT:
        return method
    return 'scatter'


def resolve_forward_paint(method=None):
    """The paint options of a grad workload (``method`` in place of
    the ``paint_method`` option, where given) plus their adjoint mode.

    Returns (cfg, mode) with mode in {'native', 'custom_vjp'}:
    'native' lets JAX reverse mode trace the kernel, 'custom_vjp'
    means :func:`make_paint` installs the analytic readout backward.
    A ``paint_method`` with neither story ('mxu') is DEMOTED to
    'scatter' — same one-chain deposit, natively adjoint via readout —
    instead of tracing into a ``jax.grad`` error deep inside the
    pipeline.  The demotion is never silent: ``source`` becomes
    ``'grad-fallback'``, the method asked for stays in
    ``winner_name``, the ``forward.grad_fallback`` counter bumps and a
    one-line WARN is logged.
    """
    cfg = {k: _global_options[k] for k in
           ('paint_method', 'paint_order', 'paint_deposit',
            'paint_chunk_size', 'paint_streams')}
    cfg['source'] = 'explicit'
    if method is not None:
        cfg['paint_method'] = method
    asked = cfg['paint_method']
    method = grad_paint_method(asked)
    if method != asked:
        cfg.update(paint_method=method, source='grad-fallback',
                   winner_name=asked)
        counter('forward.grad_fallback').add(1)
        logging.getLogger('nbodykit_tpu.forward').warning(
            "grad-mode paint: demoting %r (not differentiable) to %r "
            "for this call (forward.grad_fallback)", asked, method)
    return cfg, ('native' if method in DIFFERENTIABLE_PAINT
                 else 'custom_vjp')


def make_paint(pm, npart, resampler='cic', method=None):
    """Build a differentiable ``paint(pos, mass=1.0) -> mesh`` over
    ``pm`` for ``npart`` particles, pinned to the paint options as
    they stand now.

    The paint options are captured eagerly and re-applied via
    ``option_scope`` around every call, so what a ``jax.grad``/``jit``
    trace reads does not move with the ambient options.  Returns
    (paint_fn, cfg); cfg['adjoint_mode'] records the contract chosen
    by :func:`resolve_forward_paint`.

    ``method`` pins a specific paint kernel instead of the
    ``paint_method`` option (tests use this to exercise the custom_vjp
    path directly); a method with no adjoint story ('mxu') is a
    ValueError here — only :func:`resolve_forward_paint` may demote.
    """
    if method is not None and grad_paint_method(method) != method:
        raise ValueError(
            "paint method %r has no adjoint contract; use the "
            "resolver (method=None) for the grad fallback" % method)
    cfg, mode = resolve_forward_paint(method)
    cfg = dict(cfg, adjoint_mode=mode)
    opts = {k: cfg[k] for k in
            ('paint_method', 'paint_chunk_size', 'paint_streams')}
    cdt = jnp.dtype(pm.compute_dtype)

    def _run(pos, mass):
        with option_scope(**opts):
            return pm.paint(pos, mass, resampler=resampler)

    if mode == 'native':
        def paint_fn(pos, mass=1.0):
            return _run(pos, jnp.broadcast_to(
                jnp.asarray(mass, cdt), pos.shape[:1]))
        return paint_fn, cfg

    # box-units -> cell-units position gradient scale, per axis
    scale = jnp.asarray(np.asarray(pm.Nmesh, 'f8')
                        / np.asarray(pm.BoxSize, 'f8'), cdt)

    @jax.custom_vjp
    def _painted(pos, mass):
        return _run(pos, mass)

    def _fwd(pos, mass):
        return _run(pos, mass), (pos, mass)

    def _bwd(res, cot):
        pos, mass = res
        g = cot.astype(cdt)
        dmass = pm.readout(g, pos, resampler=resampler)
        dpos = jnp.stack(
            [pm.readout(g, pos, resampler=resampler, grad_axis=d)
             * scale[d] for d in range(3)], axis=-1)
        dpos = dpos * mass[:, None]
        return dpos.astype(pos.dtype), dmass.astype(mass.dtype)

    _painted.defvjp(_fwd, _bwd)

    def paint_fn(pos, mass=1.0):
        return _painted(pos, jnp.broadcast_to(
            jnp.asarray(mass, cdt), pos.shape[:1]))
    return paint_fn, cfg
