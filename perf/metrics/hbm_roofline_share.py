"""Kernel layer: the roofline time of one traffic step on one chip
(``perf/roofline.py``: input read once and output written once at the
HBM bandwidth, which bounds it, or the flops at the bf16 peak) over the
busiest chip's device busy time per step, in %."""

import math


def read(run):
    t = run.trace
    if t is None or not t.ops or not run.steps or not math.isfinite(
            run.min_step_s):
        return None
    busy = t.busy_s(t.busiest())
    if busy <= 0:
        return None
    return 100.0 * run.min_step_s / (busy / run.steps)
