"""FKPCatalog: joint data+randoms container for survey power spectra.

Reference: ``nbodykit/algorithms/convpower/catalog.py:30`` — a
MultipleSpeciesCatalog of ('data', 'randoms') that computes the shared
Cartesian bounding box from the randoms and hands off to FKPCatalogMesh.
"""

import numpy as np
import jax.numpy as jnp

from ...diagnostics import instrumented_jit
from ...source.catalog.species import MultipleSpeciesCatalog


def FKPWeightFromNbar(P0, nbar):
    """w_FKP = 1 / (1 + P0 * n(z)) (FKP 1994)."""
    if P0 != 0:
        return 1.0 / (1.0 + P0 * nbar)
    return 1.0


@instrumented_jit(label='convpower.extent')
def _extent(pos, sel):
    """How many rows are selected, and their per-axis minimum and
    maximum."""
    keep = sel[:, None]
    return (sel.sum(), jnp.where(keep, pos, jnp.inf).min(axis=0),
            jnp.where(keep, pos, -jnp.inf).max(axis=0))


class FKPCatalog(MultipleSpeciesCatalog):
    """data + randoms with FKP weighting and a shared bounding box.

    Parameters mirror the reference (convpower/catalog.py:75): BoxSize
    (else computed from the randoms' extent), BoxPad, P0 (to build
    FKPWeight from the ``nbar`` column).
    """

    def __init__(self, data, randoms, BoxSize=None, BoxPad=0.02,
                 P0=None, nbar='NZ'):
        if randoms is None:
            randoms = data[:0]
        MultipleSpeciesCatalog.__init__(self, ['data', 'randoms'],
                                        data, randoms)
        for name in self.species:
            if nbar not in self[name]:
                raise ValueError("column %r is not defined in %r"
                                 % (nbar, name))
        self.nbar = nbar

        for name in self.species:
            if P0 is not None:
                self[name]['FKPWeight'] = FKPWeightFromNbar(
                    P0, self[name][self.nbar])
            elif 'FKPWeight' not in self[name]:
                self[name]['FKPWeight'] = jnp.ones(len(self[name]))

        if BoxSize is not None and np.isscalar(BoxSize):
            BoxSize = np.ones(3) * BoxSize
        self.attrs['BoxSize'] = BoxSize
        if np.isscalar(BoxPad):
            BoxPad = np.ones(3) * BoxPad
        self.attrs['BoxPad'] = BoxPad

    def _define_bbox(self, position, selection, species):
        """BoxSize (padded extent) and BoxCenter from the positions of
        ``species`` (reference :110+)."""
        cat = self[species]
        pos = cat[position]
        sel = jnp.asarray(cat[selection]).astype(bool)
        # six scalars to the host, not the 1e7 x 3 positions
        count, pos_min, pos_max = _extent(pos, sel) if len(cat) \
            else (0, None, None)
        if int(count) == 0:
            raise ValueError("no selected objects in %r to define the "
                             "bounding box" % species)
        pos_min, pos_max = np.asarray(pos_min), np.asarray(pos_max)
        if np.isinf(pos_min).any() or np.isinf(pos_max).any():
            raise ValueError("infinite position range in %r" % species)

        delta = np.abs(pos_max - pos_min)
        BoxCenter = 0.5 * (pos_min + pos_max)
        if self.attrs['BoxSize'] is None:
            delta = delta * (1.0 + self.attrs['BoxPad'])
            BoxSize = np.ceil(delta)
        else:
            BoxSize = self.attrs['BoxSize']
        return BoxSize, BoxCenter

    def to_mesh(self, Nmesh=None, BoxSize=None, BoxCenter=None,
                dtype='f8', interlaced=False, compensated=False,
                resampler='cic', fkp_weight='FKPWeight',
                comp_weight='Weight', selection='Selection',
                position='Position', bbox_from_species=None, nbar=None):
        """An FKPCatalogMesh painting data - alpha*randoms.

        The mesh itself is stored real; ConvolvedFFTPower switches to
        the full-complex (c2c) spectrum automatically when odd
        multipoles are requested (the reference's dtype='c16' analog).
        """
        from .catalogmesh import FKPCatalogMesh
        if nbar is None:
            nbar = self.nbar
        if Nmesh is None:
            Nmesh = self.attrs.get('Nmesh', None)
            if Nmesh is None:
                raise ValueError("pass Nmesh to to_mesh")
        if bbox_from_species is None:
            bbox_from_species = 'randoms' if len(self['randoms']) > 0 \
                else 'data'
        box, center = self._define_bbox(position, selection,
                                        bbox_from_species)
        if BoxSize is None:
            BoxSize = box
        if BoxCenter is None:
            BoxCenter = center
        if dtype in ('c16', 'c8'):
            dtype = {'c16': 'f8', 'c8': 'f4'}[dtype]

        return FKPCatalogMesh(self, BoxSize=BoxSize, BoxCenter=BoxCenter,
                              Nmesh=Nmesh, dtype=dtype,
                              selection=selection,
                              comp_weight=comp_weight,
                              fkp_weight=fkp_weight, nbar=nbar,
                              position=position, interlaced=interlaced,
                              compensated=compensated,
                              resampler=resampler)
