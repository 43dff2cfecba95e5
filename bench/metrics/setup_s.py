"""Seconds from process start to the first timed call: JAX and the device,
the compile cache (or compiling), building the cell and one warm call."""


def read(run):
    return run.setup_s
