"""Seconds spent compiling or loading executables during set-up: the
sum of jax.monitoring's backend_compile_duration events (a persistent
cache hit counts its retrieval)."""


def read(run):
    return run.setup_compile["seconds"]
