"""End to end: nominal GFlop/s over the whole window (5 N log2 N per c2c
transform, 2.5 N log2 N per r2c or c2r), every transform dispatched in
the window counted and the window ended when the last one is ready."""


def read(run):
    return run.steps * run.flops_per_step / run.window_s / 1e9
