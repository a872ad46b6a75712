"""Seconds the process spent in backend compilation or in loading
programs from the persistent cache (``jax.monitoring``)."""


def read(run):
    return run.compile_s
