"""FKPCatalogMesh: paint the FKP density field.

Reference: ``nbodykit/algorithms/convpower/catalogmesh.py:7`` — paints
F(x) = w_fkp * [w_comp n_data - alpha w_comp n_randoms] / cellvolume,
with positions re-centered to [-L/2, L/2].
"""

import numpy as np
import jax
import jax.numpy as jnp

from ...source.mesh.species import MultipleSpeciesCatalogMesh
from ...source.mesh.catalog import CatalogMesh
from ...base.mesh import Field
from ...diagnostics import fetch, instrumented_jit, scope


@instrumented_jit(label='convpower.combine')
def _fkp_field(data, randoms, alpha, vol_per_cell):
    """``(data - alpha * randoms) / vol_per_cell`` as one program (no
    randoms: ``data / vol_per_cell``).  Op by op the two painted
    fields, their difference and the quotient were four mesh-sized
    fields whose lifetimes moved with the host's lead over the device,
    and the call's peak allocation with them
    (``fftpower._cross_power`` has the story)."""
    total = data if randoms is None else data - alpha * randoms
    return total / vol_per_cell


#: lanes of :func:`column_total`'s accumulator
_LANES = 1 << 16


@instrumented_jit(label='convpower.total')
def _lane_sums(x):
    """``x`` folded into rows of ``_LANES`` and summed row by row,
    each lane with Kahan's compensation: the sums and what they lost."""
    rows = -(-x.shape[0] // _LANES)
    x = jnp.pad(x, (0, rows * _LANES - x.shape[0])).reshape(rows, _LANES)

    def add(carry, row):
        total, lost = carry
        y = row - lost
        t = total + y
        return (t, (t - total) - y), None
    zero = jnp.zeros(_LANES, x.dtype)
    return jax.lax.scan(add, (zero, zero), x)[0]


def column_total(x):
    """Sum of a catalog column as a host float.  The survey's
    normalisation and shot noise are sums over every row of a
    catalog, and P(k) is divided by one of them: a plain f4 ``sum``
    of 2e5 equal values read 1.5e-6 off on the chip, where equal
    addends round the same way at every step.  Compensated lane sums
    on the device (elementwise adds only, so no more than a rounding
    of f4 whatever the backend's reduction does), the lanes added in
    f8 on the host."""
    total, lost = fetch(_lane_sums(jnp.asarray(x)), 'convpower.total')
    return float(total.astype('f8').sum() - lost.astype('f8').sum())


class FKPCatalogMesh(MultipleSpeciesCatalogMesh):

    def __init__(self, source, BoxSize, BoxCenter, Nmesh, dtype,
                 selection, comp_weight, fkp_weight, nbar, value='Value',
                 position='Position', interlaced=False, compensated=False,
                 resampler='cic'):
        from .catalog import FKPCatalog
        if not isinstance(source, FKPCatalog):
            raise TypeError("FKPCatalogMesh requires an FKPCatalog")

        self.attrs = dict(source.attrs)
        self.attrs['BoxSize'] = np.ones(3) * BoxSize
        self.attrs['BoxCenter'] = np.ones(3) * BoxCenter

        self._uncentered_position = position
        self.comp_weight = comp_weight
        self.fkp_weight = fkp_weight
        self.nbar = nbar

        MultipleSpeciesCatalogMesh.__init__(
            self, source=source, BoxSize=BoxSize, Nmesh=Nmesh,
            dtype=dtype, weight='_TotalWeight', value=value,
            selection=selection, position='_RecenteredPosition',
            interlaced=interlaced, compensated=compensated,
            resampler=resampler)

    def RecenteredPosition(self, name):
        """Positions shifted by -BoxCenter, i.e. into [-L/2, L/2]
        (reference :206). The ParticleMesh grid covers [0, L); shift by
        +L/2 so painting sees [0, L)."""
        pos = self.source[name][self._uncentered_position]
        center = jnp.asarray(self.attrs['BoxCenter'], pos.dtype)
        return pos - center

    def TotalWeight(self, name):
        """comp_weight * fkp_weight (reference :217)."""
        return (self.source[name][self.comp_weight]
                * self.source[name][self.fkp_weight])

    def weighted_total(self, name):
        """W = sum of selected completeness weights (reference
        weighted_total)."""
        cat = self.source[name]
        sel = cat[self.selection]
        w = cat[self.comp_weight]
        return column_total(jnp.where(sel, w, 0.0))

    def __getitem__(self, species):
        if species not in self.source.species:
            raise KeyError(species)
        cat = self.source[species]
        # provide derived columns on a shallow view of the species
        half = jnp.asarray(self.attrs['BoxSize'] / 2.0)
        view = cat.view()
        pos = self.RecenteredPosition(species)
        view['_RecenteredPosition'] = pos + jnp.asarray(
            half, pos.dtype)  # paint grid covers [0, L)
        view['_TotalWeight'] = self.TotalWeight(species)
        return CatalogMesh(
            view, Nmesh=self.attrs['Nmesh'], BoxSize=self.attrs['BoxSize'],
            dtype=self.pm.dtype.str, interlaced=self.interlaced,
            compensated=self.compensated, resampler=self.resampler,
            position='_RecenteredPosition', weight='_TotalWeight',
            value=self.value, selection=self.selection)

    def to_real_field(self):
        """The FKP density field (number density units); attrs carry
        data.W / randoms.W / alpha and per-species paint meta-data."""
        attrs = {}
        with scope('convpower.stats'):
            for name in self.source.species:
                attrs[name + '.W'] = self.weighted_total(name)
        attrs['alpha'] = attrs['data.W'] / attrs['randoms.W'] \
            if attrs['randoms.W'] > 0 else 1.0

        species = [name for name in self.source.species
                   if name == 'data' or len(self.source[name]) > 0]
        with scope('convpower.density', species=species,
                   npart=sum(len(self.source[name]) for name in species),
                   resampler=self.resampler) as sc:
            painted = {}
            for name in species:
                field = self[name].to_real_field(normalize=False)
                for k, v in field.attrs.items():
                    attrs['%s.%s' % (name, k)] = v
                painted[name] = field.value
            vol_per_cell = float(np.prod(self.attrs['BoxSize'] /
                                         self.attrs['Nmesh']))
            total = sc.done(_fkp_field(
                painted['data'], painted.get('randoms'), attrs['alpha'],
                vol_per_cell))
        attrs.pop('data.shotnoise', None)
        attrs.pop('randoms.shotnoise', None)
        return Field(total, self.pm, 'real', attrs)
