"""How the harness finds a cell's files: by name, from
``BENCHMARK.json``.

    cell          workloads[i]            of BENCHMARK.json, by --workload
    configuration configs[j]['file']      where configs[j]['name'] == cell['config']
    traffic       perf/traffic/<cell['traffic']>.json
    driver        perf/drivers/<traffic['kind']>.py     (class Driver)
    reference     perf/reference/<traffic['kind']>.py   (imported by the driver)
    layer metric  perf/layers/<metric name>.py          (function read(ctx))

A later PR adds files and entries and edits none of these."""

import importlib
import json
import os

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def benchmark():
    return load_json('BENCHMARK.json')


def cell_files(bench, workload):
    """The cell's entry, configuration, traffic and the names of the
    end-to-end and per-layer metrics it reports."""
    cells = {w['name']: w for w in bench['workloads']}
    if workload not in cells:
        raise KeyError('no workload %r in BENCHMARK.json (have %s)'
                       % (workload, ', '.join(sorted(cells))))
    cell = cells[workload]
    entry = {c['name']: c for c in bench['configs']}[cell['config']]
    config = load_json(entry['file'])
    traffic = load_json('perf', 'traffic', cell['traffic'] + '.json')

    def reported(metrics):
        return [m for m in metrics
                if workload in m.get('workloads', [workload])]
    return {'cell': cell, 'config': config, 'traffic': traffic,
            'end_to_end': reported(bench['end_to_end']),
            'per_layer': reported(bench['per_layer'])}


def driver_class(kind):
    return importlib.import_module('perf.drivers.' + kind).Driver


def layer_reader(metric):
    return importlib.import_module('perf.layers.' + metric).read
