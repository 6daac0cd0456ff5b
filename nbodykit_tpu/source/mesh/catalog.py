"""CatalogMesh: paint a catalog onto a density mesh.

Reference: ``nbodykit/source/mesh/catalog.py:11``. Capability surface:
window interpolation (nnb/cic/tsc/pcs), selection/weight/value columns,
interlacing (two half-cell-shifted meshes combined in k-space), window
compensation as a deferred complex-space action, and the 1+delta
normalization with N/W/W2/shotnoise attrs (to_real_field :155-403).

TPU redesign: no chunk/backoff loop — the whole paint (exchange +
scatter + halo) is one XLA program; the particle-count invariants
(N, W, W2) are plain global reductions.
"""

import numpy as np
import jax.numpy as jnp

from ...base.mesh import MeshSource, Field
from ...diagnostics import fetch, scope
from ...ops.window import compensation_transfer, window_support


class CatalogMesh(MeshSource):
    """A MeshSource that paints ``source``'s particles when computed.

    Parameters
    ----------
    source : CatalogSource
    Nmesh, BoxSize, dtype : mesh geometry
    resampler : window name ('nnb'|'cic'|'tsc'|'pcs')
    interlaced : bool — two-pass interlaced painting (aliasing
        suppression)
    compensated : bool — queue the Fourier-space window compensation
    position, weight, value, selection : column names
    """

    def __init__(self, source, Nmesh, BoxSize, dtype='f4', resampler='cic',
                 interlaced=False, compensated=False, position='Position',
                 weight='Weight', value='Value', selection='Selection'):
        window_support(resampler)  # validate early
        self.source = source
        self.attrs = dict(source.attrs)
        MeshSource.__init__(self, Nmesh, BoxSize, dtype=dtype,
                            comm=source.comm)
        self.resampler = resampler
        self.interlaced = interlaced
        self.compensated = compensated
        self.position = position
        self.weight = weight
        self.value = value
        self.selection = selection
        self.attrs.update(interlaced=interlaced, compensated=compensated,
                          resampler=resampler)

    @property
    def actions(self):
        actions = self._actions
        if self.compensated:
            actions = self._compensation_actions() + actions
        return actions

    def _compensation_actions(self):
        transfer = compensation_transfer(self.resampler, self.interlaced)

        def compensate(w, v):
            # named here so the layer's name travels with the action;
            # a user's own actions stay unnamed
            with scope('fftpower.transfer') as sc:
                return sc.done(transfer(w, v))
        return [('complex', compensate, 'circular')]

    def to_real_field(self, normalize=True):
        """Paint and normalize to 1 + delta; attrs gain N, W, W2,
        shotnoise, num_per_cell (reference semantics,
        source/mesh/catalog.py:155-403)."""
        pm = self.pm
        src = self.source

        pos = src[self.position]
        weight = src[self.weight] if self.weight in src else None
        value = src[self.value] if self.value in src else None
        sel = src[self.selection] if self.selection in src else None

        if weight is None:
            weight = jnp.ones(pos.shape[0])
        if value is None:
            value = jnp.ones(pos.shape[0])
        if sel is not None:
            # masked-out particles paint with zero mass (static shapes —
            # no boolean compress under a device mesh)
            weight = jnp.where(sel, weight, 0.0)

        mass = (weight * value).astype(pm.dtype)

        N = jnp.where(sel, 1.0, 0.0).sum() if sel is not None \
            else float(pos.shape[0])
        W = weight.sum()
        W2 = (weight ** 2).sum()

        if not self.interlaced:
            field = pm.paint(pos, mass, resampler=self.resampler)
        else:
            # two meshes offset by half a cell, combined in k-space
            # with the phase that re-centers the shifted one:
            # paint(shift=0.5) deposits at cell coords x/H - 1/2, i.e.
            # samples on the grid x = (j + 1/2) H, so its spectrum
            # carries e^{+ik.H/2} and the combine multiplies e^{-ik.H/2}
            f1 = pm.paint(pos, mass, resampler=self.resampler)
            f2 = pm.paint(pos, mass, resampler=self.resampler, shift=0.5)
            c1 = pm.r2c(f1)
            c2 = pm.r2c(f2)
            kx, ky, kz = pm.k_list()
            H = pm.cellsize
            kH = kx * H[0] + ky * H[1] + kz * H[2]
            combined = 0.5 * (c1 + c2 * jnp.exp(-0.5j * kH))
            field = pm.c2r(combined)

        # to host scalars for attrs (small reductions, launched before
        # the paint: the wait is for them, not for the paint)
        N, W, W2 = (float(v) for v in
                    fetch((N, W, W2), 'catalog.totals'))
        nbar = W / pm.Ntot  # mean weighted objects per cell
        shotnoise = float(np.prod(pm.BoxSize)) * W2 / W ** 2 if W > 0 \
            else 0.0

        attrs = dict(N=N, W=W, W2=W2, shotnoise=shotnoise,
                     num_per_cell=nbar)

        if normalize:
            if nbar > 0:
                field = field / nbar
            else:
                import warnings
                warnings.warn("painting an empty catalog; field set to "
                              "uniform", RuntimeWarning)
                field = jnp.ones_like(field)

        return Field(field, pm, 'real', attrs)

    def to_mesh(self):
        return self


# ---------------------------------------------------------------------------
# Named compensation functions — the reference exposes these as public
# apply-style kernels (nbodykit/source/mesh/catalog.py:453-585) that
# users pass to ``mesh.apply(..., kind='circular', mode='complex')`` in
# recipes. Each takes the circular frequencies ``w`` and the complex
# field ``v`` and divides out the window transfer. Reference naming:
# the PLAIN names are the pure Jing 2005 eq.18 sinc^p kernels (what
# get_compensation selects when interlacing already removed aliasing),
# and the *Shotnoise names are the eq.20 first-order
# aliasing-corrected forms (selected when NOT interlaced).

def _named_compensation(name, resampler, pure_sinc):
    func = compensation_transfer(resampler, interlaced=pure_sinc)
    func.__name__ = func.__qualname__ = name
    return func


CompensateCIC = _named_compensation('CompensateCIC', 'cic', True)
CompensateTSC = _named_compensation('CompensateTSC', 'tsc', True)
CompensatePCS = _named_compensation('CompensatePCS', 'pcs', True)
CompensateCICShotnoise = _named_compensation(
    'CompensateCICShotnoise', 'cic', False)
CompensateTSCShotnoise = _named_compensation(
    'CompensateTSCShotnoise', 'tsc', False)
CompensatePCSShotnoise = _named_compensation(
    'CompensatePCSShotnoise', 'pcs', False)
