"""Entry and planner layer: host seconds to build the cell's plans and
compile them, that is the plan build plus the first call of each plan
on the input (which compiles it, or loads it from the persistent
cache), bracketed by the harness. Part of ``setup_s``."""


def read(run):
    return run.plan_s
