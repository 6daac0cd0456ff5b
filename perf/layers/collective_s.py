"""Layer "exchange + all_to_all": seconds a call in which a collective
(all-to-all, all-reduce, all-gather, reduce-scatter,
collective-permute, by op name) ran on the first device in window (a).
Exists only across chips."""


def read(ctx):
    x = ctx['xplane']
    if not x or not x['ncalls'] or len(x['devices']) < 2:
        return None
    first = x['devices'][min(x['devices'])]
    return first['collective_s'] / x['ncalls']
