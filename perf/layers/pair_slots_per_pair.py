"""Layer "pair grid": candidate slots met per pair within rmax, from
the attributes ``slots`` and ``pairs`` of the library's span
``paircount.tiles`` in window (b), the median over its calls: what the
cell decomposition wastes (7.5 for cubes of 27 cells of rmax around a
sphere; more where a block of primaries spans several cells, less
where a cell is wider than rmax).  ``None`` where the program writes
no such span."""

import statistics


def read(ctx):
    ratios = [r['attrs']['slots'] / float(r['attrs']['pairs'])
              for r in ctx.get('spans') or ()
              if r['name'] == 'paircount.tiles'
              and r.get('attrs', {}).get('pairs')]
    return statistics.median(ratios) if ratios else None
