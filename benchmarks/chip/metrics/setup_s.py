"""Process start to the window's start: weights, warm-up, compiles."""


def read(run):
    return run.setup_s
