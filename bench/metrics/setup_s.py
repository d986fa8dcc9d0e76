"""Process start to the first window step: objects, weights, compiles
(from the persistent cache after a cell's first run) and the warm-up."""


def read(run):
    return run.setup_s
