"""Process start to the window opening: loading, weight generation,
compilation or loading from the compile cache, and warm-up."""


def read(run):
    return run.setup_s
