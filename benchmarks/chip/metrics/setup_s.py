"""Set-up: from the start of the process to the opening of the window
(loading, making the weights, compiling or loading programs, warm-up)."""


def read(run):
    return run.setup_s
