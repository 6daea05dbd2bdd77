"""Device layer: the share of the traced window in which the busiest
chip ran no operation, in %: 1 - (union of its op intervals / window)."""


def read(run):
    t = run.trace
    if t is None or not t.ops or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s(t.busiest()) / t.window_s)
