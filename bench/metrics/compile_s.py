"""Seconds of lowering and backend compiling during set-up, from JAX's
monitoring events (a cache hit still lowers)."""


def read(rec):
    return rec["compile_s"]
