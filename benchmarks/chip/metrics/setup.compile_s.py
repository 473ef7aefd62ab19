"""Seconds of set-up spent in JAX's compile path (tracing, lowering and
compiling or loading from the persistent cache), by JAX's own
``/jax/core/compile/`` duration events."""


def read(record, trace, ctx):
    return record.get("setup_compile_s")
