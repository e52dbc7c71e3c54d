"""Process start to the window's first batch: imports, the model, weights
and pool from the seed, bind, and the warm-up `fit` with its compiles or
cache reads."""


def read(run):
    return run.setup_s
