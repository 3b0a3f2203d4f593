"""Seconds from process start until the window opens: imports, weights,
cluster and predictor, and the warm-up of every shape the cell uses
(compiles, or loads from the compile cache)."""


def read(run):
    return run.setup_s
