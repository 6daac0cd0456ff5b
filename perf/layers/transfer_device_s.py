"""Layer "transfer": device self time a call of the ops under the library's
``nbk.`` scopes of that layer, window (a), first device
(``perf/lib/scopes.py``: an op's own ``op_name`` scope, else that
of the host annotation its program was launched under)."""

from perf.lib import scopes


def read(ctx):
    return scopes.layer_s(ctx, 'transfer')
