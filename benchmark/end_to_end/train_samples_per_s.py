"""All rows of all batches finished in the window, over the whole window:
from the window's first batch to the parameters being ready after `fit`
returned. Host clock."""


def read(run):
    r = run.result
    return r["batches"] * r["rows_per_batch"] / (r["t_close"] - r["t_open"])
