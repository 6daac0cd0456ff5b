"""Generate docs/API.md: one reference entry per ``nbodykit_tpu.lab``
export (signature + summary + public methods), grouped by subsystem.

Run from the repo root:  python docs/gen_api.py
"""

import inspect
import os
import sys
import types

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import jax
jax.config.update('jax_platforms', 'cpu')

GROUPS = [
    ("Runtime and options", [
        'set_options', 'setup_logging', 'timer', 'CurrentMesh',
        'use_mesh', 'cpu_mesh', 'tpu_mesh', 'single_device_mesh']),
    ("Particle mesh", ['ParticleMesh', 'FieldMesh', 'MeshSource']),
    ("Catalogs", [
        'CatalogSource', 'ArrayCatalog', 'RandomCatalog',
        'UniformCatalog', 'LogNormalCatalog', 'HaloCatalog',
        'MultipleSpeciesCatalog', 'SubVolumesCatalog', 'DemoHaloCatalog',
        'CSVCatalog', 'BinaryCatalog', 'BigFileCatalog', 'HDFCatalog',
        'FITSCatalog', 'TPMBinaryCatalog', 'Gadget1Catalog',
        'FileCatalogFactory']),
    ("Meshes", [
        'CatalogMesh', 'LinearMesh', 'ArrayMesh', 'BigFileMesh',
        'MultipleSpeciesCatalogMesh', 'FKPCatalogMesh']),
    ("Power spectra and correlation functions", [
        'FFTPower', 'ProjectedFFTPower', 'FFTCorr', 'Bispectrum',
        'ConvolvedFFTPower', 'FKPPower', 'FKPCatalog', 'FKPWeightFromNbar',
        'FFTRecon']),
    ("Pair counting and clustering statistics", [
        'SimulationBoxPairCount', 'SurveyDataPairCount', 'PairCountBase',
        'SimulationBox2PCF', 'SurveyData2PCF', 'AnalyticUniformRandoms',
        'WedgeBinnedStatistic', 'SimulationBox3PCF', 'SurveyData3PCF']),
    ("Group finding and catalogs of groups", [
        'FOF', 'KDDensity', 'CylindricalGroups', 'RedshiftHistogram',
        'FiberCollisions']),
    ("Results containers", ['BinnedStatistic']),
    ("Cosmology", [
        'Cosmology', 'Planck13', 'Planck15', 'WMAP5', 'WMAP7', 'WMAP9',
        'LinearPower', 'NonLinearPower', 'HalofitPower',
        'ZeldovichPower', 'CorrelationFunction', 'EHPower',
        'NoWiggleEHPower', 'FNLGalaxyPower', 'LinearNbody', 'cosmology']),
    ("Halo occupation (HOD)", [
        'HODModel', 'Zheng07Model', 'Leauthaud11Model', 'Hearin15Model',
        'HODMock']),
    ("Mesh filters and transforms", [
        'filters', 'TopHat', 'Gaussian', 'transform', 'meshtools']),
    ("Batch processing and IO", ['TaskManager', 'io', 'IO']),
]


def _sig(obj):
    try:
        # signature(cls) resolves __init__/__new__ properly (calling
        # it on cls.__init__ hands back object.__init__'s wrapper for
        # classes without their own constructor)
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return '(...)'


def _summary(obj):
    doc = inspect.getdoc(obj) or ''
    lines = doc.strip().splitlines()
    out = []
    for ln in lines:
        if not ln.strip():
            break
        out.append(ln.strip())
    return ' '.join(out)


def _methods(cls):
    rows = []
    for name, m in sorted(vars(cls).items()):
        if name.startswith('_'):
            continue
        if isinstance(m, property):
            rows.append((name, '(property)',
                         (_summary(m.fget) or '').split('. ')[0]))
        elif callable(m):
            rows.append((name, _sig(m), _summary(m).split('. ')[0]))
    return rows


def main():
    import nbodykit_tpu.lab as lab

    covered = set()
    out = [
        "# API reference",
        "",
        "Every public name in `nbodykit_tpu.lab` (the user-facing namespace, mirroring the reference's `nbodykit.lab`), grouped by subsystem. Generated by `docs/gen_api.py`; regenerate after changing public signatures. Deeper narrative docs: [COMPONENTS.md](COMPONENTS.md) (design map), [EXAMPLES.md](EXAMPLES.md) (cookbook), [MIGRATING.md](MIGRATING.md) (coming from nbodykit), [PERF.md](PERF.md) (performance), [OBSERVABILITY.md](OBSERVABILITY.md) (tracing/metrics/crash-safe telemetry — `set_options(diagnostics=...)`, the `nbodykit_tpu.diagnostics` span/metric API, compile telemetry via `instrumented_jit`, multi-host trace merging/straggler/critical-path analysis via `--analyze`, bench regression tracking via `--regress`, and the `nbodykit-tpu-doctor` console script), [RESILIENCE.md](RESILIENCE.md) (checkpointed, retrying, fault-injectable execution — `nbodykit_tpu.resilience`, the `Supervisor`/`CheckpointStore`/`default_ladder` API, the `set_options(faults=...)` injection harness), [LINT.md](LINT.md) (the `nbodykit-tpu-lint` interprocedural shard-safety static analyzer — NBK1xx collectives incl. NBK103 collective-order deadlock detection / NBK2xx compile hygiene / NBK3xx precision / NBK4xx trace safety / NBK5xx memory-donation with the `--memory-report` symbolic peak table, suppressions, the `lint_baseline.json` gate), [BISPECTRUM.md](BISPECTRUM.md) (the hybrid FFT/direct-pairwise bispectrum estimator — the Scoccimarro triangle-count FFT path, the blocked MXU `pairblock` direct path and the `bspec_method` option).",
        "",
    ]
    for title, names in GROUPS:
        out.append("## %s" % title)
        out.append("")
        for name in names:
            obj = getattr(lab, name, None)
            if obj is None:
                continue
            covered.add(name)
            if isinstance(obj, types.ModuleType):
                out.append("### `%s` (module)" % name)
                out.append("")
                out.append(_summary(obj))
                pub = getattr(obj, '__all__', None) or [
                    n for n, v in sorted(vars(obj).items())
                    if not n.startswith('_')
                    and not isinstance(v, types.ModuleType)]
                out.append("")
                out.append("Public names: %s"
                           % ', '.join('`%s`' % n for n in pub[:30]))
            elif inspect.isclass(obj):
                out.append("### `%s%s`" % (name, _sig(obj)))
                out.append("")
                out.append(_summary(obj))
                rows = _methods(obj)
                if rows:
                    out.append("")
                    out.append("| method | signature | summary |")
                    out.append("|---|---|---|")
                    for r in rows:
                        out.append("| `%s` | `%s` | %s |"
                                   % (r[0], r[1].replace('|', r'\|'),
                                      r[2].replace('|', r'\|')))
            elif callable(obj):
                out.append("### `%s%s`" % (name, _sig(obj)))
                out.append("")
                out.append(_summary(obj))
            else:
                out.append("### `%s`" % name)
                out.append("")
                out.append("%r" % (obj,))
            out.append("")

    # anything exported but not grouped above must still appear
    leftovers = [n for n in sorted(vars(lab))
                 if not n.startswith('_') and n not in covered]
    if leftovers:
        out.append("## Other exports")
        out.append("")
        for name in leftovers:
            obj = getattr(lab, name)
            if isinstance(obj, types.ModuleType):
                out.append("- `%s` (module): %s"
                           % (name, _summary(obj).split('. ')[0]))
            elif callable(obj):
                out.append("- `%s%s`: %s"
                           % (name, _sig(obj), _summary(obj).split('. ')[0]))
            else:
                out.append("- `%s`" % name)
        out.append("")

    path = os.path.join(os.path.dirname(__file__), 'API.md')
    with open(path, 'w') as f:
        f.write('\n'.join(out))
    n_entries = sum(1 for line in out if line.startswith('### ')) + \
        len(leftovers)
    print("wrote %s: %d reference entries, %d leftover names"
          % (path, n_entries, len(leftovers)))


if __name__ == '__main__':
    main()
