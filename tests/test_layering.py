"""The drawing has no arrow upward (ISSUE 29): options are read from
the package root, ``algorithms -> base/source -> pmesh -> ops,
parallel``, and ``serve``, ``ingest``, ``forward`` above them.  Read
from the source by ``ast``, so a lazy import inside a function counts
like one at the top of the file.

Deliberate and left alone: ``pmesh`` and ``parallel/`` import
``resilience.faults`` / ``.integrity`` (safety code: the fault points
and the tier-0 checks sit where the data is).
"""

import ast
import importlib
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'nbodykit_tpu')

ABOVE = ('tune', 'serve', 'forward', 'ingest')
# (what, its files under nbodykit_tpu/, the subpackages it may not import)
LAYERS = [
    ('ops', 'ops', ABOVE + ('algorithms',)),
    ('parallel', 'parallel', ABOVE + ('algorithms',)),
    ('pmesh', 'pmesh.py', ABOVE),
    ('base', 'base', ABOVE),
    ('source', 'source', ABOVE),
]


def _files(rel):
    path = os.path.join(PKG, rel)
    if os.path.isfile(path):
        return [path]
    return sorted(os.path.join(d, f) for d, _, fs in os.walk(path)
                  for f in fs if f.endswith('.py'))


def _imports(source, rel):
    """Every module of the package that ``source``, the text of the
    file at ``rel`` below the package root, imports: dotted names
    below the root with the imported name last, and the line."""
    pkg = rel[:-3].split(os.sep)[:-1]
    tree = ast.parse(source, rel)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split('.')[0] == 'nbodykit_tpu':
                    yield '.'.join(a.name.split('.')[1:]), node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = pkg[:len(pkg) - (node.level - 1)]
                mod = base + (node.module.split('.')
                              if node.module else [])
            elif (node.module or '').split('.')[0] == 'nbodykit_tpu':
                mod = node.module.split('.')[1:]
            else:
                continue
            # `from .. import serve` names the subpackage in `names`
            for a in node.names:
                yield '.'.join(mod + [a.name]), node.lineno


@pytest.mark.parametrize('layer', LAYERS, ids=[la[0] for la in LAYERS])
def test_no_import_from_above(layer):
    _, rel, forbidden = layer
    files = _files(rel)
    assert files, rel
    bad = []
    for path in files:
        rel = os.path.relpath(path, PKG)
        with open(path) as f:
            bad += ['%s:%d imports %s' % (rel, line, mod)
                    for mod, line in _imports(f.read(), rel)
                    if mod.split('.')[0] in forbidden]
    assert not bad, bad


def test_the_reader_sees_lazy_and_relative_imports():
    """The guard on the guard: a lazy ``from ..serve.request import x``
    one package down is seen as ``serve.request.x``."""
    src = ("def f():\n    from ..serve.request import shape_class\n"
           "    from .. import forward\n    import nbodykit_tpu.ingest\n"
           "    from .window import window_support\n    import numpy\n")
    found = sorted(m for m, _ in _imports(
        src, os.path.join('ops', 'probe.py')))
    assert found == ['forward', 'ingest', 'ops.window.window_support',
                     'serve.request.shape_class']


def test_tuner_is_gone():
    for mod in ('nbodykit_tpu.tune', 'nbodykit_tpu.tune.resolve',
                'nbodykit_tpu.tune.space'):
        with pytest.raises(ImportError):
            importlib.import_module(mod)
    assert not os.path.exists(os.path.join(PKG, 'tune'))
    assert not os.path.exists(os.path.join(os.path.dirname(PKG),
                                           'TUNE_CACHE.json'))
