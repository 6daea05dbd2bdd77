"""Exchange layer: the part of each chip's collective time in which no
other operation ran on that chip, per traffic step, on the chip where it
is largest. None where no collective ran."""


def read(run):
    t = run.trace
    if t is None or not run.steps or not t.ops:
        return None
    if max(t.collective_s(d) for d in t.ops) <= 0:
        return None
    return 1e3 * max(t.exposed_s(d) for d in t.ops) / run.steps
