"""Set-up seconds in JAX's ``/jax/core/compile/*`` spans: tracing,
lowering, compiling or fetching from the persistent cache (union)."""


def read(record):
    return record.compile_s
