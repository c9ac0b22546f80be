"""Backend compilations inside the measured window: should be 0, since
set-up warms up every program the window runs."""


def read(r):
    return r.compile.count["window"]
