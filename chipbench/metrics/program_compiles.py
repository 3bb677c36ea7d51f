"""Programs compiled, or loaded from the persistent compile cache, inside
the program's own chunks during the window, as the program counts them:
``ServerStats.compiles`` (``repro_server_compiles_total``, from JAX's
``/jax/core/compile/backend_compile_duration`` events on the compiling
thread), window delta.  A program without the counter gives nothing to
read."""


def read(record):
    n = record.counters.get("compiles")
    return None if n is None else float(n)
