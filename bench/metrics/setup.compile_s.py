"""Seconds of backend compilation during set-up, persistent-cache reads
included (JAX monitoring events)."""


def read(rec):
    return rec.setup_compile_s
