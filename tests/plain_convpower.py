"""ConvolvedFFTPower in plain numpy, f8: the tier-1 tests' name for
``perf/reference/lab_convpower.py``, the one copy of the reference
(its docstring has the estimator and every departure from the
papers).  It shares no code with ``nbodykit_tpu``, and the benchmark
holds the survey cell to the same file at the cell's own size."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from perf.reference.lab_convpower import (   # noqa: E402,F401
    REAL_YLM, in_slabs, reference_convpower, round_to_bfloat16)
