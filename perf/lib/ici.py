"""Bytes a collective must send over the interchip links, from its
shapes alone, and the published interchip bandwidth of one chip, keyed
by ``device_kind``.

Kept with the benchmark, beside ``work.py`` and ``peaks.json``, so
that no PR that claims a gain can change them."""

#: Google Cloud documentation, 'TPU v5e' system architecture page: 1600
#: Gbit/s of interchip interconnect per chip (the figure
#: ``peaks.json``'s ``_source`` cites), the total over all of a chip's
#: links, taken as the bytes a second one chip can send.  A 2x2 host
#: wires half of a chip's links, so a share of this figure reads low
#: there and cannot pass 100%.
#: Indexed, never ``.get``: an unknown kind is an error, not a default.
ICI_BYTES_PER_S = {'TPU v5 lite': 1600e9 / 8, 'TPU v5e': 1600e9 / 8}


def a2a_bytes(nmesh, chips, itemsize=4):
    """Bytes one chip must send in the transpose of a slab r2c of
    ``nmesh``^3 over ``chips`` devices: after the two local passes a
    device holds ``N1 x N0/P x (N2/2 + 1)`` complex numbers, and all
    but its own 1/P of them belong to another device."""
    n, p = int(nmesh), int(chips)
    block = n * (n // p) * (n // 2 + 1) * 2 * itemsize
    return block * (p - 1) // p
