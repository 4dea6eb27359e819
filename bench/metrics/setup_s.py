"""Set-up seconds: process start to the first timed call (host clock).
Loading, data or weights, compiling or reading the compile cache, and the
warm-up calls all fall inside it."""


def read(run):
    return run.setup_s
