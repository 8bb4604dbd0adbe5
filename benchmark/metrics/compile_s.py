"""Seconds the XLA backend spent compiling or loading programs from the
persistent cache during set-up (jax.monitoring events)."""


def read(record):
    return record["spans"]["compile_s"]
