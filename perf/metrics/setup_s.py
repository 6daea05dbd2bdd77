"""End to end: seconds from the start of the process to the first timed
transform: runtime start, plan build and compile (from the persistent
cache after a cell's first run), input made from the seed, warm-up."""


def read(run):
    return run.setup_s
