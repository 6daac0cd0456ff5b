"""Layer "compile": seconds the backend spent compiling, or loading
programs from the persistent cache, inside window (a): the library's
``xla.compile.backend_s`` histogram, sum after - before.  0 is the
healthy reading; a path that builds a new jit on every call shows
here."""


def read(ctx):
    return ctx.get('compile_s_window_a')
