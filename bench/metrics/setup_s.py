"""Process start to the first timed request: weights, warm-up, compiles
or cache loads."""


def read(run):
    return run.setup_s
