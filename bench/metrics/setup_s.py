"""Process start to window start: imports, chip, weights, compiles, warm-up."""


def read(run):
    return run.setup_s
