"""The table of peaks, keyed by ``device_kind``."""

import json
import os


def peaks_for(device_kind):
    """The published peaks of one chip; an unknown kind is an error,
    never a default."""
    with open(os.path.join(os.path.dirname(__file__), 'peaks.json')) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith('_'):
        raise KeyError('no peaks for device kind %r in perf/lib/peaks.json'
                       % (device_kind,))
    return table[device_kind]
