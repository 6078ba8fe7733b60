"""Process start to the start of the window: JAX start-up, the weight
draw, loading or compiling the programs, the warm-up and the pre-roll."""


def read(run):
    return run.setup_s
