"""Set-up time of the run: process start to the opening of the window
(imports, weights, warm-up and any compilation)."""


def read(run):
    return run.setup_s
