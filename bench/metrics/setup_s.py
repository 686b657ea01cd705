"""Process start to the first due request: imports, weights, warm-up,
cache loads."""


def read(run):
    return run.setup_s
