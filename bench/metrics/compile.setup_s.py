"""Seconds of backend compilation (XLA and Mosaic) during set-up, from JAX's
``backend_compile_duration`` events: near 0 when every program of the cell
comes from the persistent compilation cache."""


def read(r):
    return r.compile.secs["setup"]
