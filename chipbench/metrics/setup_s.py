"""setup_s: process start to the first timed call, compiles included."""


def read(run):
    return run.setup_s
