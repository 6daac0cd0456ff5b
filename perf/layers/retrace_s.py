"""Layer "compile": seconds a call of window (b) that jax spent
tracing, lowering and compiling again (or loading from the persistent
cache): the durations of the library's spans ``compile.trace``,
``compile.lower`` and ``compile.backend`` over the window's calls.
0 is the healthy reading; a path that builds a new jit in every call
shows here, with all three stages where ``compile_s_in_window`` holds
the last alone."""

from perf.lib import hostledger


def read(ctx):
    return hostledger.retrace_s(ctx)
