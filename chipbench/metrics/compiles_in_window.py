"""Programs compiled, or loaded from the persistent compile cache, inside
the measured window: JAX's ``/jax/core/compile/backend_compile_duration``
events, which the harness listens to.  Every one is a first call of a
shape that the warm-up did not reach."""


def read(record):
    return float(record.compiles)
